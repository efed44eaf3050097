"""``benchmark/tests/test_trace.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_trace import *  # noqa: F401,F403
