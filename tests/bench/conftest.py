"""The benchmark's own CPU tests (``bench/`` beside ``src/``): the
benchmark's modules are imported by their names, as ``bench/run.py``
imports them."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


@pytest.fixture(autouse=True)
def _run_from_the_checkout(monkeypatch):
    monkeypatch.chdir(BENCH.parent)
