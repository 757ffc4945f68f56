"""Tests of the chip benchmark, run on the CPU by explicit path:

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests

They import the program only to compare the benchmark's copies with it
and to drive a tiny run end to end."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_weights():
    """The tiny configuration trained once, for every test that runs it:
    trained well enough to end its answers and accept drafts."""
    import tiny
    from bench import cells

    cell = tiny.cell()
    return cells.family(cell).train(cell, 5)


@pytest.fixture
def trained(monkeypatch, tiny_weights):
    """Runs in this test take the shared tiny weights instead of training."""
    from bench import train

    monkeypatch.setattr(train, "train", lambda *a, **k: tiny_weights)
    return tiny_weights
