#!/usr/bin/env python3
"""One traced window of a cell, read by phase. Not part of a benchmark
run; it runs in one process on the chip.

    python3 benchmarks/chip/phases.py --workload mt-retro.sbs.batch \
        --seed 7 --seconds 20

Set-up and window as ``run.py --trace 1`` has them. After the window,
with the engine still alive, it reads what the result line of a run
cannot: the megastep's device time by named scope (the trace joined to
``StreamingEngine.op_scopes()`` by ``bench.scopes``), the device's idle
time by host span, and the scheduler's slot occupancy. Prints one JSON
line; times are ms per megastep (scopes) or per pump iteration (idle).
Exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402
from bench import cells, scopes, trace  # noqa: E402
from bench import train as training  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    cell = cells.load(R.ROOT, a.workload)
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("phases.py: JAX found no TPU", file=sys.stderr)
        return 2
    R.cache_settings(jax)
    params, _ = training.train(R.model_cfg(cell), cell.config["train"],
                               a.seed)
    n_slots = R.slots(cell)
    src, order = R.queries(cell, a.seed)
    eng = R.make_engine(cell, params)
    R.warm(eng, cell, src, order, n_slots)
    s0 = eng.loop_stats()
    tdir = tempfile.mkdtemp(prefix="chipbench-phases-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    rec = R.window(eng, cell, src, order, a.seed, a.seconds, n_slots)
    jax.profiler.stop_trace()
    s1 = eng.loop_stats()
    op_scopes = eng.op_scopes()
    path = trace.find_xplane(tdir)
    red = trace.reduce(path)
    prog = scopes.program_ops(path)
    shutil.rmtree(tdir, ignore_errors=True)

    runs, it = max(prog["runs"], 1), max(rec.iterations, 1)
    split = scopes.by_scope(prog["ops"], op_scopes)
    per_op = defaultdict(float)
    for name, own in prog["ops"]:
        per_op[name] += 1e3 * own / runs
    slot_steps = s1["slot_steps"] - s0["slot_steps"]
    capacity = s1["capacity_steps"] - s0["capacity_steps"]
    scoped = sum(v for k, v in split["top"].items() if k != scopes.UNSCOPED)
    out = {
        "cell": cell.name, "seed": a.seed, "pump_iterations": rec.iterations,
        "megasteps": prog["runs"],
        "megastep_ms": 1e3 * prog["seconds"] / runs,
        "scoped_share": scoped / prog["seconds"] if prog["seconds"] else None,
        "scope_ms": {k: 1e3 * v / runs for k, v in sorted(
            split["paths"].items(), key=lambda kv: -kv[1])},
        "top_ms": {k: 1e3 * v / runs for k, v in sorted(
            split["top"].items(), key=lambda kv: -kv[1])},
        "idle_ms": {k: 1e3 * v / it for k, v in red["idle"]},
        "idle_share": 1.0 - red["busy_s"] / red["window_s"],
        "slot_steps": slot_steps, "capacity_steps": capacity,
        "slot_occupancy": slot_steps / capacity if capacity else None,
        # every megastep op of at least 10 us per megastep, with its scope
        "ops_ms": [[n, ms, op_scopes.get(n, scopes.UNSCOPED)]
                   for n, ms in sorted(per_op.items(), key=lambda kv: -kv[1])
                   if ms >= 0.01],
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
