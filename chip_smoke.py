#!/usr/bin/env python3
"""Bring-up check of the serving engine on a TPU.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # only the multi-chip paths

One chip. The retrosynthesis Molecular Transformer (``mt-retro``: 6+6
layers, d_model 256, 8 heads, d_ff 2048) with random weights from
``--seed`` is served through one paged ``StreamingEngine`` (page size 16)
whose slot groups hold all four decoding modes at the paper's shapes
(N_beams 5, N_d 25, DL 10, max_new 96, max_src 128). It checks

  - speculative greedy tokens == greedy tokens,
  - speculative-beam top-5 == beam top-5,
  - engine output == the one-shot decoders of ``repro.core``,
  - the Pallas paged-decode kernel's tokens == the XLA page view's, with
    the kernel compiled by Mosaic (``tpu_custom_call`` in the megastep),
  - four ``/v1/generate`` requests through a ``FrontDoorServer`` finish
    with the engine's tokens.

Each identity is asserted at the served (default) matmul precision when
it holds there. Where it does not, the agreement rate at default precision
is printed and the identity is asserted under
``jax.default_matmul_precision("highest")`` instead.

Four chips (``--chips 4``). The same model, speculative and
speculative-beam groups: the engine sharded over a (2, 2) mesh of all four
chips, and a ``FleetRouter`` over four one-chip replicas served from this
process (one ``FrontDoorServer`` per chip), each against the same engine
on one chip, token for token.

Exits non-zero on any failure, and at once when JAX finds no TPU. The last
line of stdout is ``{"ok": true, "device": {...}}`` on success only.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MODES = ("greedy", "speculative", "beam", "speculative_beam")
FOUR_CHIP_MODES = ("speculative", "speculative_beam")
# speculative beam search with an empty draft: exactly one beam-search step
# per iteration, so its top-5 must equal beam search's
SBS_DL0 = "speculative_beam@draft_len=0"


def refuse(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def check(ok: bool, msg: str) -> None:
    """A failed check ends the run non-zero (asserts vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


# ---------------------------------------------------------------- counters
class Compiles:
    """Compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events, so each phase can report its own share."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0, h0, m0 = time.perf_counter(), self.secs, self.hits, self.misses
        yield
        print(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self.secs - c0:.3f} cache_hits={self.hits - h0} "
              f"cache_misses={self.misses - m0}", flush=True)


def precision(p: str | None):
    import jax

    return (jax.default_matmul_precision(p) if p
            else contextlib.nullcontext())


# ------------------------------------------------------------------ serving
def rows(r) -> list[list[int]]:
    """A finished request's candidates, each trimmed to its length."""
    return [[int(x) for x in r.tokens[k][:int(r.lengths[k])]]
            for k in range(r.tokens.shape[0])]


def serve_all(params, cfg, tok, queries, ecfg, modes):
    """Every query once in every mode through one ``StreamingEngine``,
    plus, where the speculative-beam group serves, the first half of the
    queries once more with per-request ``draft_len=0`` (key ``SBS_DL0``;
    half, because that group's queue sets the run's length). Returns
    ``(engine, {(mode, i): SlotResult})``."""
    from repro.serving import GenerationParams, StreamingEngine

    eng = StreamingEngine(params, cfg, tok, ecfg)
    handles = {(m, i): eng.submit(q, mode=m)
               for m in modes for i, q in enumerate(queries)}
    if "speculative_beam" in modes:
        handles.update({(SBS_DL0, i): eng.submit(
            q, mode="speculative_beam",
            params=GenerationParams(draft_len=0))
            for i, q in enumerate(queries[:max(1, len(queries) // 2)])})
    done = eng.serve()
    out = {k: done[int(h)] for k, h in handles.items()}
    bad = {k: str(r.status) for k, r in out.items() if r.status != "finished"}
    check(not bad, f"requests did not finish: {bad}")
    return eng, out


def one_shot(params, cfg, tok, queries, ecfg):
    """{(mode, i): candidate strings} from ``ReactionEngine``, the
    one-shot decode loops of ``repro.core`` jitted per batch shape."""
    from repro.serving import ReactionEngine

    out = {}
    for mode in MODES:
        ref = ReactionEngine(params, cfg, tok,
                             dataclasses.replace(ecfg, mode=mode))
        if mode in ("greedy", "speculative"):
            preds = ref.predict(queries)
        else:
            preds = [ref.predict_topn(q) for q in queries]
        for i, p in enumerate(preds):
            out[mode, i] = p.smiles
    return out


def agreement(pairs) -> tuple[int, int]:
    pairs = list(pairs)
    return sum(a == b for a, b in pairs), len(pairs)


def assert_identity(name: str, measure) -> str:
    """``measure(precision) -> (n_equal, n)``. Asserts at the served
    precision where the identity holds there, else prints the default
    agreement and asserts under "highest". Returns the precision used."""
    eq, n = measure(None)
    print(f"identity {name}: {eq}/{n} agree at default precision",
          flush=True)
    if eq == n:
        return "default"
    eq, n = measure("highest")
    print(f"identity {name}: {eq}/{n} agree at highest precision",
          flush=True)
    check(eq == n, f"{name}: {n - eq}/{n} differ even at highest precision")
    return "highest"


def mode_stats(eng, out, modes) -> None:
    print(f"engine: {eng.scheduler.n_steps} scheduler steps, "
          f"{eng.n_dispatches} dispatches", flush=True)
    for m in modes:
        rs = [r for (mm, _), r in out.items() if mm == m]
        gen = sum(int(r.lengths[0]) for r in rs)
        acc = sum(r.accepted for r in rs)
        calls = sum(r.n_calls for r in rs)
        print(f"mode {m}: requests={len(rs)} tokens={gen} "
              f"decoder_calls={calls} acceptance={acc / max(gen, 1):.4f}",
              flush=True)


def megastep_hlo(eng) -> str:
    """Compiled HLO of the engine's decode megastep."""
    return eng._megastep_fn.lower(eng.params,
                                  eng.scheduler.state).compile().as_text()


def generate_all(port: int, queries, jobs: list[tuple[str, int]]) -> list:
    """POST every ``(mode, query index)`` to ``/v1/generate``
    concurrently; returns each request's event list."""
    from repro.serving.server import sse_events

    events: list = [None] * len(jobs)

    def one(j):
        m, i = jobs[j]
        req = ({"mode": "speculative_beam", "draft_len": 0} if m == SBS_DL0
               else {"mode": m})
        events[j] = sse_events("127.0.0.1", port,
                               {"query": queries[i], **req}, timeout=900.0)

    ts = [threading.Thread(target=one, args=(i,)) for i in range(len(jobs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return events


def check_done(events, want: list[list[int]], what: str) -> dict:
    done = events[-1] if events else {}
    check(done.get("event") == "done" and done.get("status") == "finished",
          f"{what}: no finished done event: {events[-2:]}")
    check(done["tokens"] == want, f"{what}: tokens differ from the engine's")
    return done


# ---------------------------------------------------------------- one chip
def front_door(eng, out, queries, comp: Compiles) -> None:
    """One ``/v1/generate`` request per mode through a ``FrontDoorServer``
    over ``eng``; each must finish with the engine's own tokens."""
    from repro.serving import FrontDoorServer, ServerConfig

    jobs = [(m, i % len(queries)) for i, m in enumerate(MODES)]
    with comp.phase("server"):
        srv = FrontDoorServer(eng, ServerConfig(port=0)).start()
        try:
            events = generate_all(srv.port, queries, jobs)
        finally:
            srv.shutdown()
    for job, evs in zip(jobs, events):
        check_done(evs, rows(out[job]), f"server {job}")
    print(f"server: {len(jobs)} /v1/generate requests finished with the "
          f"engine's tokens", flush=True)


def one_chip(params, cfg, tok, queries, ecfg, comp: Compiles) -> None:
    import gc

    import jax

    from repro.models.attention import use_paged_kernel

    runs: dict = {}
    kernel_hlo: dict = {}

    def engine_run(p, kernel=False):
        """Results of one engine run at precision ``p``. Only results are
        kept: one engine's cache and megastep fill most of the chip, so
        the previous engine is collected before the next is built."""
        if (p, kernel) in runs:
            return runs[p, kernel]
        gc.collect()
        use_paged_kernel(kernel)
        try:
            with comp.phase(f"{'paged-kernel' if kernel else 'serve'}"
                            f"[{p or 'default'}]"), precision(p):
                eng, out = serve_all(params, cfg, tok, queries, ecfg, MODES)
                if kernel:
                    kernel_hlo[p] = megastep_hlo(eng)
        finally:
            use_paged_kernel(False)
        mode_stats(eng, out, MODES)
        if (p, kernel) == (None, False):
            # the drive thread runs at the served precision, so the front
            # door is checked against this run whatever the others need
            front_door(eng, out, queries, comp)
        runs[p, kernel] = out
        return out

    def pairs(p, a, b):
        out = engine_run(p)
        return ((rows(out[a, i]), rows(out[b, i]))
                for i in range(len(queries)) if (a, i) in out)

    assert_identity("speculative == greedy",
                    lambda p: agreement(pairs(p, "speculative", "greedy")))
    assert_identity("speculative_beam(draft_len=0) top-5 == beam top-5",
                    lambda p: agreement(pairs(p, SBS_DL0, "beam")))
    # with drafts, SBS ranks candidates of unequal lengths and can find a
    # different (even higher-scoring) top-5 than beam search — a search
    # difference, not rounding, so it is measured, not asserted
    for k in (1, 5):
        eq, n = agreement((a[:k], b[:k]) for a, b in
                          pairs(None, "speculative_beam", "beam"))
        print(f"speculative_beam(draft_len={ecfg.draft_len}) top-{k} == "
              f"beam top-{k}: {eq}/{n} at default precision", flush=True)

    refs: dict = {}

    def vs_one_shot(p):
        out = engine_run(p)
        if p not in refs:
            with comp.phase(f"one-shot[{p or 'default'}]"), precision(p):
                refs[p] = one_shot(params, cfg, tok, queries, ecfg)
        return agreement(([tok.decode(t) for t in rows(out[k])], refs[p][k])
                         for k in refs[p])

    assert_identity("engine == one-shot decoders", vs_one_shot)

    def vs_kernel(p):
        out, out_k = engine_run(p), engine_run(p, kernel=True)
        return agreement((rows(out_k[k]), rows(out[k])) for k in out)

    used = assert_identity("paged kernel == XLA page view", vs_kernel)
    hlo = kernel_hlo[None if used == "default" else "highest"]
    check("tpu_custom_call" in hlo, "paged kernel was not compiled by Mosaic")
    print("paged kernel: tpu_custom_call in the compiled megastep",
          flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


# -------------------------------------------------------------- four chips
def four_chips(params, cfg, tok, queries, ecfg, comp: Compiles) -> None:
    import gc

    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.serving import (FleetConfig, FleetRouter, FrontDoorServer,
                               ServerConfig, StreamingEngine)

    devs = jax.devices()[:4]
    runs: dict = {}

    def on(mesh):
        return dataclasses.replace(ecfg, mesh=mesh)

    def single(p):
        if p not in runs:
            gc.collect()      # one engine at a time fills a chip
            with comp.phase(f"one-chip[{p or 'default'}]"), precision(p):
                runs[p] = serve_all(
                    params, cfg, tok, queries,
                    on(make_serving_mesh((1, 1), devices=devs[:1])),
                    FOUR_CHIP_MODES)[1]
        return runs[p]

    def vs_sharded(p):
        ref = single(p)
        gc.collect()
        with comp.phase(f"sharded-2x2[{p or 'default'}]"), precision(p):
            eng, out = serve_all(params, cfg, tok, queries,
                                 on(make_serving_mesh((2, 2), devices=devs)),
                                 FOUR_CHIP_MODES)
        mode_stats(eng, out, FOUR_CHIP_MODES)
        print(f"sharded shard_stats: {eng.shard_stats()}", flush=True)
        return agreement((rows(out[k]), rows(ref[k])) for k in ref)

    assert_identity("(2, 2) mesh == one chip", vs_sharded)

    # four replicas in THIS process, one per chip: a chip belongs to one
    # process, so child processes could not share the host's chips
    ref = single(None)
    jobs = list(ref)
    gc.collect()
    with comp.phase("fleet-4x1"):
        servers = [FrontDoorServer(
            StreamingEngine(params, cfg, tok,
                            on(make_serving_mesh((1, 1), devices=[d]))),
            ServerConfig(port=0)).start() for d in devs]
        router = FleetRouter([("127.0.0.1", s.port) for s in servers],
                             FleetConfig(probe_interval_s=0.5)).start()
        try:
            events = generate_all(router.port, queries, jobs)
        finally:
            router.shutdown()
            for s in servers:
                s.shutdown()
    served_by: dict = {}
    for job, evs in zip(jobs, events):
        done = check_done(evs, rows(ref[job]), f"fleet {job}")
        served_by[done["replica"]] = served_by.get(done["replica"], 0) + 1
    print(f"fleet: {len(jobs)} requests over {len(servers)} one-chip "
          f"replicas, per replica {sorted(served_by.items())}", flush=True)
    check(len(served_by) == len(servers),
          f"router used {len(served_by)} of {len(servers)} replicas")
    for i, d in enumerate(devs):
        stats = d.memory_stats() or {}
        print(f"device {i} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}", flush=True)


# ----------------------------------------------------------------- entry
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds before a stuck run dumps its threads' "
                         "stacks and exits non-zero")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(args.deadline, exit=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        refuse("the repro package is not next to this script; run it from "
               "a checkout of the repository")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        refuse(f"JAX found no TPU (it runs on {devices[0].platform}); this "
               f"check runs only on the chip")
    if len(devices) < args.chips:
        refuse(f"--chips {args.chips} needs {args.chips} TPU chips, JAX "
               f"found {len(devices)}")

    from repro.configs.mt import retro_config, with_vocab
    from repro.data import SyntheticReactionDataset
    from repro.launch.runtime import enable_compile_cache
    from repro.models import seq2seq as s2s
    from repro.serving import EngineConfig

    cache_dir = enable_compile_cache()
    comp = Compiles()
    print(f"jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}"
          f", compile cache {cache_dir}", flush=True)

    ds = SyntheticReactionDataset(args.queries, seed=args.seed,
                                  direction="retro")
    tok = ds.tokenizer
    cfg = with_vocab(retro_config(), tok.vocab_size)
    params = s2s.init(jax.random.PRNGKey(args.seed), cfg)
    queries = [ds.pair(i)[0] for i in range(args.queries)]
    modes = MODES if args.chips == 1 else FOUR_CHIP_MODES
    ecfg = EngineConfig(paged=True, page_size=16,
                        mode_groups={m: 4 for m in modes})
    print(f"model {cfg.name}: {cfg.n_encoder_layers}+{cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; {len(queries)} queries; beams "
          f"{ecfg.n_beams}, drafts {ecfg.n_drafts} x {ecfg.draft_len}, "
          f"max_new {ecfg.max_new}, max_src {ecfg.max_src}", flush=True)

    if args.chips == 1:
        one_chip(params, cfg, tok, queries, ecfg, comp)
    else:
        four_chips(params, cfg, tok, queries, ecfg, comp)
    print(f"compile cache: {comp.hits} hits, {comp.misses} misses, "
          f"{comp.secs:.3f} s compiling", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
