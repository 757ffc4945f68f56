"""The megastep's device time by named scope, and the idle readers, on a
trace recorded on the CPU here and now."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import cells, scopes, trace
from conftest import ROOT
from repro.serving.tracing import op_scopes

CPU = dict(device_plane=lambda n: n == "/host:CPU",
           op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
           module_line=lambda n: False)


def scoped(x):
    with jax.named_scope("decoder"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("select"):
        return jnp.argmax(y, axis=-1)


def other(x):
    return jnp.argmax(jnp.tanh(x @ x), axis=-1)


def _names(text):
    return {line.split(" = ")[0].strip().lstrip("%").replace("ROOT ", "")
            for line in text.splitlines() if " = " in line}


def test_by_scope_counts_one_program_by_scope(tmp_path):
    """Three runs of ``scoped`` and three of ``other``, which compiles to
    instructions of the same names: only ``scoped``'s are counted, and
    they fall under its two scopes."""
    x = jnp.ones((256, 256))
    f, g = jax.jit(scoped), jax.jit(other)
    text = f.lower(x).compile().as_text()
    shared = _names(text) & _names(g.lower(x).compile().as_text())
    f(x).block_until_ready()
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        f(x).block_until_ready()
        g(x).block_until_ready()
    jax.profiler.stop_trace()
    prog = scopes.program_ops(trace.find_xplane(str(tmp_path)),
                              "jit_scoped", **CPU)
    assert prog["runs"] == 3 and prog["seconds"] > 0
    counted = [n for n, _ in prog["ops"]]
    seen_shared = shared & set(counted)
    assert seen_shared, (shared, counted)
    for name in seen_shared:
        assert counted.count(name) == 3, name
    split = scopes.by_scope(prog["ops"], op_scopes(text))
    assert split["top"]["decoder"] > 0 and split["top"]["select"] > 0
    assert sum(split["top"].values()) == pytest.approx(prog["seconds"])
    assert set(split["paths"]) <= {"decoder", "select", scopes.UNSCOPED}


def test_by_scope_puts_unknown_ops_unscoped():
    out = scopes.by_scope([("fusion.1", 2.0), ("copy.3", 1.0),
                           ("fusion.2", 0.5)],
                          {"fusion.1": "decoder/self_attn",
                           "fusion.2": "reorder"})
    assert out["top"] == {"decoder": 2.0, scopes.UNSCOPED: 1.0,
                          "reorder": 0.5}
    assert out["paths"]["decoder/self_attn"] == 2.0


class Rec:
    def __init__(self, iterations):
        self.iterations = iterations


class Ctx:
    def __init__(self, red, iterations=10):
        self.trace, self.rec = red, Rec(iterations)


def test_idle_readers():
    readout = cells.reader("idle_readout_ms.batch")
    host = cells.reader("idle_host_ms.batch")
    idle = [("serve/readout", 0.04), ("bench/pump", 0.03),
            ("serve/wait", 0.01), ("serve/evict", 0.02),
            ("serve/schedule", 0.005), ("host/other", 0.1)]
    ctx = Ctx({"idle": idle}, iterations=10)
    assert readout(ctx) == pytest.approx(5.0)
    assert host(ctx) == pytest.approx(2.5)
    # a parent program without the new spans reads what it has
    assert host(Ctx({"idle": [("serve/readout", 0.04)]})) == 0.0
    for read in (readout, host):
        assert read(Ctx(None)) is None
        assert read(Ctx({"idle": idle}, iterations=0)) is None


def test_phases_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/phases.py", "--workload",
         "mt-retro.sbs.batch", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
