"""Tier-1 collects the benchmark's own tests: every test of
``benchmarks/tests/test_counts.py``, under its own name, with that
directory's fixtures. No logic here; the file is one of nine so that
``--dist loadfile`` spreads them over the workers."""

from benchmarks.tests.conftest import _from_root, mistral_serve, mistral_train  # noqa: F401
from benchmarks.tests.test_counts import *  # noqa: F401,F403
