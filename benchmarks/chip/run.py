#!/usr/bin/env python3
"""One run of one chip-benchmark cell.

    python3 benchmarks/chip/run.py --workload mt-retro.sbs.batch \
        --seed 7 --seconds 30 --trace 0

Set-up (counted in ``setup_s``): JAX start-up, weights trained on the
device from ``--seed``, the serving front built and its programs warmed
on the cell's own shapes. The configuration's ``family`` key names its
model code (``bench/families/<family>.py``, ``seq2seq`` where absent);
the mix's ``front`` key names the front (``bench/fronts/<front>.py``,
``engine``, the in-process pump, where absent). The window: the front serves the cell's traffic
mix for ``--seconds``. After it: device memory is read on every chip of
the cell, the front freed, and a sample of the served requests compared
with the plain reference.
The last line of stdout is one JSON object; with ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` (window
traced by the profiler) its per-layer metrics.

Exits 2 with no result line when the cell, its family or front, or the
program cannot be found, when JAX finds no TPU, or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from bench import cells, drive, flops, scopes, trace, traffic  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def cache_settings(jax) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    every program kept, nothing evicted (eviction reads per-entry access
    stamps that a machine-wide size limit may have left missing)."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def refuse(msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr)
    sys.exit(2)


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) reads."""
    cell: cells.Cell
    rec: drive.Record
    setup_s: float
    loop: dict
    flops: float
    peak: dict
    device: dict
    trace: dict | None


class Compiles:
    """Programs compiled or loaded from the persistent cache, counted from
    JAX's monitoring events; one inside the window is a fault of set-up."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def queries(cell: cells.Cell, fam, seed: int):
    """(the family's query pool for the cell's mix, the seed's order)."""
    src = fam.pool(cell)
    return src, traffic.order(len(src), seed)


def window_flops(cell, fam, rec) -> float:
    total = 0.0
    for rid, (t, r) in rec.seen.items():
        if t <= rec.seconds and rec.finished(r):
            total += fam.query_flops(cell, rec.queries[rid], r)
    return total


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    shown = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()
             if not k.startswith("control.")}
    ok = all(k in limits and v["value"] <= limits[k] for k, v in shown.items())
    return ok and "finished" not in numbers, shown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = cells.load(ROOT, args.workload)
        traffic.validate(cell.traffic)
        cells.family(cell), cells.front(cell)
    except (cells.NotFound, KeyError, ValueError) as e:
        refuse(f"cannot load cell {args.workload!r}: {e}")
    src_dir = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src_dir, "repro")):
        refuse(f"the program is not at {src_dir}")
    sys.path.insert(0, src_dir)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        refuse(f"JAX found no TPU (platform {devs[0].platform})")
    chips = int(cell.workload["chips"])
    if len(devs) < chips:
        refuse(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    cache_settings(jax)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devs[:chips])
    print(json.dumps(result), flush=True)
    return 0


def run(cell: cells.Cell, seed: int, seconds: float, traced: bool,
        devs, control: bool = False) -> dict:
    """Set-up, window and check of one run; returns the result line.
    ``control`` (calibration only) adds the bfloat16 control's readings."""
    import jax

    compiles = Compiles(jax)
    # ---- set-up
    fam = cells.family(cell)
    params, losses = fam.train(cell, seed)
    src, order = queries(cell, fam, seed)
    front = cells.front(cell).Front(cell, fam, params, src, order, devs)
    c0 = front.counters()
    n_compiles0 = compiles.n
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if tdir:
        # host spans (TraceAnnotation) but no Python call tracing: that
        # would slow the host path it is meant to attribute
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START

    # ---- the window
    rec = front.window(src, order, seed, seconds)
    if tdir:
        jax.profiler.stop_trace()
    in_window_compiles = compiles.n - n_compiles0
    c1 = front.counters()
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    red = None
    if tdir:
        path = trace.find_xplane(tdir)
        red = trace.reduce(path)
        op_scopes = front.op_scopes()
        if op_scopes is not None:
            prog = scopes.program_ops(path)
            red["scopes"] = dict(scopes.by_scope(prog["ops"], op_scopes),
                                 runs=prog["runs"])
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    loop = {k: v - c0[k] for k, v in c1.items()}
    loop["iterations"] = rec.iterations
    ctx = Context(cell=cell, rec=rec, setup_s=setup_s, loop=loop,
                  flops=0.0, peak=flops.peak(devs[0].device_kind),
                  device=device, trace=red)
    front.close()
    del front
    gc.collect()
    return finish(cell, fam, ctx, params, seed, losses, in_window_compiles,
                  control)


def finish(cell, fam, ctx, params, seed, losses, in_window_compiles,
           control=False) -> dict:
    """Metrics, the check, the stderr lines; returns the result line."""
    rec, traced = ctx.rec, ctx.trace is not None
    ctx.flops = window_flops(cell, fam, rec)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if ("layer" in m) != traced:
            continue
        v = cells.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = fam.check_numbers(cell, params, rec, seed, control)
    ok, shown = verdict(numbers, cell.limits)
    attempted = rec.attempted()
    failed = rec.failed()
    if traced:
        print("trace lines (events): " + ", ".join(
            f"{k} ({v})" for k, v in ctx.trace["lines"].items()),
            file=sys.stderr)
        print("trace programs (count, mean ms): " + ", ".join(
            f"{k} ({len(v)}, {1e3 * sum(v) / len(v):.3f})"
            for k, v in ctx.trace["modules"].items()), file=sys.stderr)
    print(f"cell {cell.name} seed {seed}: {kind} metrics, "
          f"{len(rec.in_window())} results in the window, "
          f"{rec.iterations} pump iterations, drain {rec.drain_s:.3f} s, "
          f"compiles in the window {in_window_compiles}, "
          f"pump gap max {1e3 * max(rec.pump_gaps, default=0):.3f} ms, "
          f"final training loss {float(losses[-1]):.5f}", file=sys.stderr)
    for k, v in shown.items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    result = {"correct": bool(ok and not failed), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics,
              "device": ctx.device}
    if ctx.trace is not None:
        result["breakdown"] = trace.breakdown(ctx.trace)
    if control:
        result["control"] = {k: v for k, v in numbers.items()
                             if k.startswith("control.")}
    result["checks"] = shown
    sys.stderr.flush()
    return result


if __name__ == "__main__":
    sys.exit(main())
