"""Tier-1 collects the benchmark's own tests: every test of
``benchmarks/tests/test_jamba.py``, under its own name, with that
directory's fixtures. No logic here; the file is one of eleven so that
``--dist loadfile`` spreads them over the workers."""

from benchmarks.tests.conftest import _from_root  # noqa: F401
from benchmarks.tests.test_jamba import *  # noqa: F401,F403
