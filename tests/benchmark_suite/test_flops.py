"""``benchmark/tests/test_flops.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_flops import *  # noqa: F401,F403
