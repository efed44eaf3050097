"""One file per architecture family, found by the ``family`` key of a
configuration.  A family says how the program builds the model and its
loss, how a batch is drawn, which plain reference it is compared with,
and how many operations and bytes its step needs.  Every function
takes the configuration (``cfg``) and the cell's traffic as dicts."""
