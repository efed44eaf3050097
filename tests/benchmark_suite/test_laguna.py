"""``benchmark/tests/test_laguna.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_laguna import *  # noqa: F401,F403
