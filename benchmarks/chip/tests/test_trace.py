"""Trace reduction, on a trace recorded on the CPU here and now."""

import time

import jax
import jax.numpy as jnp

from bench import trace


def test_reduce_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/pump"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("serve/readout"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    red = trace.reduce(
        trace.find_xplane(str(tmp_path)),
        device_plane=lambda n: n == "/host:CPU",
        op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        module_line=lambda n: False)
    assert 0.06 <= red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert any(name.startswith("dot") for name in red["ops"])
    idle = dict(red["idle"])
    # the sleeps are idle time spent in serve/readout
    assert idle.get("serve/readout", 0.0) >= 0.05
    assert sum(idle.values()) + red["busy_s"] == \
        __import__("pytest").approx(red["window_s"], rel=1e-6)
    out = trace.breakdown(red)
    assert set(out) == {"device_ops", "idle_gaps"}
    assert len(out["device_ops"]) <= 10 and out["idle_gaps"][0][1] > 0


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
