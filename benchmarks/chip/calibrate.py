#!/usr/bin/env python3
"""Chip-side calibration of the benchmark's fixed numbers. Not part of a
benchmark run; each subcommand runs in one process on the chip.

    calibrate.py train   --workload W --steps 500,1000 --seed S
        held-out top-1 accuracy and tokens per call after each count of
        training steps (the configuration's ``train.steps``)
    calibrate.py slots   --workload W --slots 2,4,8 --seconds T --seed S
        queries_per_s and device memory at each slot count
    calibrate.py control --workload W --seeds 1,2,3 --seconds T
        per seed, the compared numbers of the program and of the bfloat16
        control (``checks/<cell>.json`` limits)

Each prints one JSON line per point on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402
from bench import cells, stats, traffic  # noqa: E402
from bench import train as training  # noqa: E402


def ints(s):
    return [int(x) for x in s.split(",") if x]


class Ctx:
    def __init__(self, rec):
        self.rec = rec


def top1(cell, eng, src, tgt, idx) -> dict:
    """Serve ``idx`` of the pool once; exact top-1 matches and tokens per
    call against the pool's targets."""
    mode = cell.traffic["mode"]
    hs = {int(eng.submit(src[i], mode=mode)): i for i in idx}
    t = time.perf_counter()
    done = eng.serve(realtime=True)
    wall = time.perf_counter() - t
    hit = tok = calls = 0
    for rid, i in hs.items():
        r = done[rid]
        L = int(r.lengths[0])
        want = [int(x) for x in tgt[i] if x]
        hit += [int(x) for x in r.tokens[0][:L]] == want
        tok += L
        calls += int(r.n_calls)
    return {"top1": hit / len(idx), "tokens_per_call": tok / max(calls, 1),
            "serve_s": wall, "n": len(idx)}


def cmd_train(a, cell, out):
    import jax

    e = cell.config["engine"]
    src, tgt = traffic.pool(cell.config["direction"], cell.traffic,
                            e["max_src"], e["max_new"])
    idx = traffic.order(len(src), a.seed)[:a.n]
    # one chunk first: the timings below are of training, not compiling
    training.train(R.model_cfg(cell), cell.config["train"], a.seed,
                   steps=cell.config["train"]["chunk"])
    for steps in ints(a.steps):
        tcfg = dict(cell.config["train"], steps=steps)
        t = time.perf_counter()
        params, losses = training.train(R.model_cfg(cell), tcfg, a.seed)
        jax.block_until_ready(params)
        train_s = time.perf_counter() - t
        eng = R.make_engine(cell, params)
        R.warm(eng, cell, src, idx, R.slots(cell))
        row = {"steps": steps, "train_s": train_s,
               "loss_last100": float(losses[-100:].mean()),
               **top1(cell, eng, src, tgt, idx)}
        out(row)
        del eng, params
        gc.collect()


def cmd_slots(a, cell, out):
    import jax

    params, _ = training.train(R.model_cfg(cell), cell.config["train"], a.seed)
    src, order = R.queries(cell, a.seed)
    for n in ints(a.slots):
        eng = R.make_engine(cell, params, n)
        t = time.perf_counter()
        R.warm(eng, cell, src, order, n)
        warm_s = time.perf_counter() - t
        rec = R.window(eng, cell, src, order, a.seed, a.seconds, n)
        ms = jax.devices()[0].memory_stats() or {}
        done = stats.finished_in_window(Ctx(rec))
        out({"n_slots": n, "warm_s": warm_s,
             "queries_per_s": len(done) / a.seconds,
             "iteration_ms": 1e3 * a.seconds / max(rec.iterations, 1),
             "tokens_per_call": stats.tokens_per_call(Ctx(rec)),
             "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
             "bytes_in_use": ms.get("bytes_in_use")})
        del eng, rec
        gc.collect()


def cmd_control(a, cell, out):
    import jax

    for seed in ints(a.seeds):
        t = time.perf_counter()
        res = R.run(cell, seed, a.seconds, False, jax.devices()[:1],
                    control=True)
        out({"seed": seed, "wall_s": time.perf_counter() - t,
             "correct": res["correct"], "attempted": res["attempted"],
             "failed": res["failed"],
             "metrics": {k: v["value"] for k, v in res["metrics"].items()},
             "checks": {k: v["value"] for k, v in res["checks"].items()},
             "control": res["control"]})
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cmd", choices=("train", "slots", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--steps", default="")
    ap.add_argument("--slots", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--n", type=int, default=64)
    a = ap.parse_args(argv)
    cell = cells.load(R.ROOT, a.workload)
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        R.refuse("calibration runs on the chip")
    R.cache_settings(jax)

    def out(row):
        print(json.dumps(row), flush=True)

    {"train": cmd_train, "slots": cmd_slots,
     "control": cmd_control}[a.cmd](a, cell, out)


if __name__ == "__main__":
    main()
