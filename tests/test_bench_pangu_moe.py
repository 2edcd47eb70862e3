"""Tier-1 collects the benchmark's own tests: every test of
``benchmarks/tests/test_pangu_moe.py``, under its own name, with that
directory's fixtures. No logic here; the file is one of ten so that
``--dist loadfile`` spreads them over the workers."""

from benchmarks.tests.conftest import _from_root  # noqa: F401
from benchmarks.tests.test_pangu_moe import *  # noqa: F401,F403
