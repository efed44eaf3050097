"""``benchmark/tests/test_scopes.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_scopes import *  # noqa: F401,F403
