"""``benchmark/tests/test_runs.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_runs import *  # noqa: F401,F403
