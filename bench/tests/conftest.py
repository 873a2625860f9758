"""The benchmark's own tests: ``python -m pytest bench/tests`` from the root.

They run on the CPU at small sizes, never on the chip, and write only
under pytest's temporary directories.
"""

import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def scratch_env(tmp_path, monkeypatch):
    """A private trace store and data directory for one test."""
    monkeypatch.setenv("REPRO_TRACE_CACHE", str(tmp_path / "store"))
    return tmp_path
