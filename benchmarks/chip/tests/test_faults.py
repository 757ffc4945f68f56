"""A whole run on the CPU (the look for a chip skipped) with the timed
path broken underneath: ``correct`` must come out false for each fault a
serving cell can have."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as R
import tiny
from bench import chem, flops


@pytest.fixture(autouse=True)
def cpu_peak(monkeypatch):
    monkeypatch.setattr(flops, "peak", lambda kind: {"bf16_flops_per_s": 1e12})


def unchanged(eng, monkeypatch):
    """The step returns its state unchanged (its bundle read from it)."""
    def same(params, g):
        return g, eng._make_bundle(g, eng._slot_counts(g), None)

    monkeypatch.setattr(eng, "_megastep_fn", jax.jit(same))


def altered(eng, monkeypatch):
    """A token altered where it is produced (the slot's read-out)."""
    orig = eng._read_slot

    def read(state, slot):
        out = orig(state, slot)
        toks = np.array(out["tokens"])
        toks[0, min(1, int(out["lengths"][0]) - 1)] = chem.UNK
        return dict(out, tokens=toks)

    monkeypatch.setattr(eng, "_read_slot", read)


def off_argmax(monkeypatch):
    """Draft verification accepts one draft token past the first that is
    not the model's argmax. Planted before the step is traced."""
    from repro.core import session

    orig = session._accept_lengths

    def accept(greedy_tok, drafts, mask):
        n = orig(greedy_tok, drafts, mask)
        return jnp.where(mask, jnp.minimum(n + 1, drafts.shape[-1]), 0)

    monkeypatch.setattr(session, "_accept_lengths", accept)


# (mode, fault, planted before set-up)
CASES = [
    ("speculative", None, False), ("speculative_beam", None, False),
    ("speculative", unchanged, False), ("speculative_beam", unchanged, False),
    ("speculative", altered, False), ("speculative_beam", altered, False),
    ("speculative", off_argmax, True),
]


@pytest.mark.parametrize(
    "mode,fault,early", CASES,
    ids=[f"{m}-{f.__name__ if f else 'sound'}" for m, f, _ in CASES])
def test_fault_makes_run_incorrect(monkeypatch, trained, mode, fault, early):
    cell = tiny.cell(mode, "backlog", drain_s=2)
    window = R.window

    def broken(eng, *a, **k):
        fault(eng, monkeypatch)
        return window(eng, *a, **k)

    if fault is not None and early:
        fault(monkeypatch)
    elif fault is not None:
        monkeypatch.setattr(R, "window", broken)
    res = R.run(cell, 2**35 + 11, 2.0, False, jax.devices()[:1])
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
