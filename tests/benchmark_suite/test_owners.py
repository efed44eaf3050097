"""``benchmark/tests/test_owners.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_owners import *  # noqa: F401,F403
