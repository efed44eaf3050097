"""``benchmark/tests/test_contract.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_contract import *  # noqa: F401,F403
