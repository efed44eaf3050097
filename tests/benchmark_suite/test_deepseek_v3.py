"""``benchmark/tests/test_deepseek_v3.py`` under tier-1 (see ``conftest.py``)."""

from benchmark.tests.test_deepseek_v3 import *  # noqa: F401,F403
