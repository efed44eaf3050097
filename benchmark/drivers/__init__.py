"""One file per kind of measured window, found by the ``driver`` key of
a cell.  ``train.py``: a window of training steps."""
