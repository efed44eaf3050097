"""``benchmark/tests/test_kimi_linear.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_kimi_linear import *  # noqa: F401,F403
