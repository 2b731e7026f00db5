"""Tier-1 collects ``benchmarks/tests/test_host_gap_readers.py`` through this
file: every case there is a case here, counted on its own.  One thin
file per file of ``benchmarks/tests/``, because the tier-1 command
hands one file to one worker (``--dist loadfile``)."""

import os
import sys

import pytest

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
for _p in (_BENCH, os.path.join(_BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
pytest.register_assert_rewrite("test_host_gap_readers")

from test_host_gap_readers import *  # noqa: E402,F401,F403
