"""Shared set-up of the benchmark's own tests (``python -m pytest
benchmarks/chip``): they run on the CPU, at the tiny sizes of
``data/configs``, in a scratch checkout made per test."""

import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip.tests import record_trace  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout with the test cells (``data/BENCHMARK.json``), the
    benchmark's code and a link to the program under test."""
    return record_trace.scratch_root(ROOT, tmp_path / "checkout")
