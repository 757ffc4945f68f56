"""Serving throughput under a Poisson request stream — the scenario the
continuous-batching engine exists for (and the headline metric of the
paper's follow-up, arXiv 2508.01459).

For each decoding mode, N requests arrive as an open-loop Poisson process
and stream through a StreamingEngine with S decode slots; we report
requests/sec and p50/p95 end-to-end latency (arrival -> tokens out,
including queueing). Speculative modes commit several tokens per shared
step, so at equal slot count they clear the queue faster — the
requests/sec column is the paper's Table 2/3 speedup restated as a
serving metric.

The run also exercises the paged KV cache with in-flight mode mixing: the
oversubscription demo serves a MIXED session (greedy + speculative slot
groups sharing one page pool) on a pool deliberately smaller than the
contiguous-row layout would need for the same slot count — admission
gates on free pages across both groups, short requests release their
pages early, and the session sustains more slots than the equivalent
contiguous HBM budget allows.

``--modes mixed`` (in the default set) adds the in-flight mode-mixing
workload: ONE session with per-mode slot groups (greedy + speculative +
beam) sharing a cache serves a round-robin request mix, reporting overall
and per-mode req/s + latency — and asserting zero recompilation after the
per-group warmup.

``--modes decoder_greedy decoder_speculative`` (in the default set) runs
the decoder-only backend: a reduced decoder-only LM served through the
same StreamingEngine with prompt-lookup drafts and chunked ragged prefill
(``repro.serving.backend.DecoderOnlyBackend``) — the bench gate tracks
these modes like any other.

``--modes planning`` (in the default set) simulates a Retro*-style
retrosynthetic expansion loop on the decoder-only backend with
cross-request prefix page sharing: a tree of ``submit_child`` requests
whose prompts extend their parents', served twice — once with the radix
prefix cache, once cold — reporting routes/sec, the prefix-cache hit
rate, and pages allocated per request vs the cold control (the shared
run must allocate strictly fewer).

``--modes priority_mix`` (in the default set) exercises the request front
door's priority scheduling: one session, one slot group, the same Poisson
stream split into high- and low-priority halves. The per-class
``queue_delay`` percentiles make the SLO behavior visible in the perf
trajectory — high-priority requests overtake the low-priority backlog at
every admission.

``--modes overload`` (in the default set) replays the
``benchmarks/load_gen.py`` trace — Poisson BURSTS, heavy-tailed prompt
lengths, mid-stream cancels, a deadline-carrying high class — through the
full ``OverloadPolicy`` (priority aging + deadline-aware preemption +
load shedding) on the closed-loop step clock, so ``slo_high`` /
``slo_low`` / ``shed_rate`` / the best-effort starvation bound are
deterministic and CI gates them (``--slo-threshold`` /
``--shed-threshold`` in ``check_regression.py``).

``--modes fleet`` (in the default set) measures the replica-router layer
end to end: subprocess replicas (``repro.serving.fleet.replica``) behind a
``FleetRouter``, a concurrent request wave through one replica vs two
(aggregate req/s, p50/p95/p99, the 2-replica speedup — asserted >= 1.5x
on multi-core hosts; on a single-core host the replicas time-slice one
CPU, so the scaling assert relaxes to a sanity floor and the measured
ratio is reported), then a mid-run replica-KILL drill on a fresh 2-replica
fleet: queued requests must fail over and finish on the survivor —
``reroute_success_rate`` joins the CI gate (``--reroute-threshold`` in
``check_regression.py``).

``--modes sharded`` (in the default set) serves the speculative paged
workload on a ``StreamingEngine`` partitioned over a (data=2, model=2)
device mesh (forced host devices on CPU): slot groups and the page pool
shard over the data axis, parameters over the model axis, one donated
jitted dispatch per steady-state iteration. Reports aggregate req/s plus
per-shard admissions, peak page occupancy, and the admit/page balance
ratios the bench gate enforces (``--imbalance-threshold`` in
``check_regression.py`` — a drift above the ceiling means placement
stopped spreading load).

Results are printed AND written as machine-readable ``BENCH_serving.json``
(req/s, p50/p95 latency + queue delay, peak/capacity cache bytes, slots
resident) so the perf trajectory is tracked across PRs;
``benchmarks/check_regression.py`` diffs a fresh run against the committed
baseline in CI (the bench gate: req/s floors AND p95 latency ceilings).

    PYTHONPATH=src python benchmarks/serving_throughput.py \
        [--requests 16] [--rate 2.0] [--slots 2] [--seed 0] \
        [--json BENCH_serving.json] [--no-paged-demo]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the sharded mode partitions a real (data=2, model=2) host mesh: force 8
# CPU devices BEFORE the repro imports below pull in jax. Idempotent when
# the runner already exports its own XLA_FLAGS (same pattern as
# tests/conftest.py).
_FORCE_DEVICES = "--xla_force_host_platform_device_count=8"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FORCE_DEVICES).strip()

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import trained_model
from repro.core import SessionSpec
from repro.launch.runtime import enable_compile_cache
from repro.serving import EngineConfig, OverloadPolicy, StreamingEngine
from repro.serving.engine import _mode_shape

MODES = ("greedy", "speculative", "beam", "speculative_beam", "mixed",
         "decoder_greedy", "decoder_speculative", "priority_mix",
         "planning", "overload", "sharded", "fleet")
# the mixed workload's slot groups: cheap greedy probes + speculative
# forward predictions + beam retrosynthesis expansions in ONE session
# (requests round-robin over the groups)
MIXED_GROUPS = ("greedy", "speculative", "beam")
# decoder-only workload: reduced arch served via DecoderOnlyBackend
DECODER_ARCH = "smollm-135m"
DECODER_EOS = 2


def _latency_stats(results) -> dict:
    """p50/p95 end-to-end latency AND queue delay (arrival -> admission)
    for a result set — queue delay is the SLO-facing half of latency."""
    lat = np.sort([r.latency for r in results]) if results else np.zeros(1)
    qd = np.sort([r.queue_delay for r in results]) if results else np.zeros(1)
    return {
        "p50": float(np.percentile(lat, 50)),
        "p95": float(np.percentile(lat, 95)),
        "queue_delay_p50": float(np.percentile(qd, 50)),
        "queue_delay_p95": float(np.percentile(qd, 95)),
    }


def _warmup(eng, query) -> None:
    """Compile the step + admit once, on a throwaway session."""
    eng.submit(query)
    eng.serve()
    eng.reset()


def _loop_row(eng, results) -> dict:
    """Host-loop dispatch accounting for the fused-megastep drive: jitted
    dispatches per generated token / per scheduler iteration (steady state
    == 1.0: one megastep and nothing else) and the host step-gap (seconds
    between consecutive bundle syncs) percentiles. ``check_regression.py``
    gates ``dispatches_per_token`` and ``step_gap_p95_s``."""
    loop = eng.loop_stats()
    gen = sum(int(r.lengths[0]) for r in results)
    dispatches = loop["dispatches_per_iteration"] * loop["n_iterations"]
    return {
        "n_iterations": loop["n_iterations"],
        "dispatches_per_iteration": loop["dispatches_per_iteration"],
        "dispatches_per_token": dispatches / max(gen, 1),
        "steady_iterations_one_dispatch":
            loop["steady_iterations_one_dispatch"],
        "step_gap_p50_s": loop["step_gap_p50_s"],
        "step_gap_p95_s": loop["step_gap_p95_s"],
    }


def _engine_row(eng, results) -> dict:
    """The per-mode result row every single-session workload shares:
    throughput, latency/queue-delay percentiles, acceptance, residency."""
    makespan = max(r.completed for r in results)
    acc = sum(r.accepted for r in results)
    gen = sum(int(r.lengths[0]) for r in results)
    return {
        "rps": len(results) / makespan,
        **_latency_stats(results),
        "steps": eng.scheduler.n_steps,
        "acceptance": acc / max(gen, 1),
        "n_slots": eng.n_slots,
        "slots_resident": eng.scheduler.max_resident,
        "preemptions": eng.scheduler.n_preemptions,
        "cache": eng.cache_footprint(),
        **_loop_row(eng, results),
    }


def run_mode(mode: str, params, cfg, tok, queries, arrivals, args):
    ecfg = EngineConfig(mode=mode, draft_len=args.draft_len,
                        n_drafts=args.n_drafts, n_beams=args.n_beams,
                        max_new=args.max_new, max_src=96,
                        n_slots=args.slots)
    eng = StreamingEngine(params, cfg, tok, ecfg)
    _warmup(eng, queries[0])

    for q, t in zip(queries, arrivals):
        eng.submit(q, arrival=float(t))
    results = list(eng.serve(realtime=True).values())
    return {"mode": mode, **_engine_row(eng, results)}


def run_priority_mix(params, cfg, tok, queries, arrivals, args):
    """Priority/SLO demo: ONE speculative session, the same Poisson
    stream, alternating high/low priority. High-priority arrivals
    overtake the queued low-priority backlog at every admission, which
    shows up as a lower queue-delay p95 for the high class — the number
    the bench gate tracks."""
    ecfg = EngineConfig(mode="speculative", draft_len=args.draft_len,
                        n_drafts=args.n_drafts, max_new=args.max_new,
                        max_src=96, n_slots=args.slots)
    eng = StreamingEngine(params, cfg, tok, ecfg)
    _warmup(eng, queries[0])

    classes = ["high" if i % 2 == 0 else "low"
               for i in range(len(queries))]
    cls_of = {}
    for q, t, cls in zip(queries, arrivals, classes):
        h = eng.submit(q, arrival=float(t),
                       priority=1 if cls == "high" else 0)
        cls_of[int(h)] = cls
    by_rid = eng.serve(realtime=True)
    results = list(by_rid.values())
    per_cls = {cls: [r for rid, r in by_rid.items() if cls_of[rid] == cls]
               for cls in ("high", "low")}
    return {
        "mode": "priority_mix",
        **_engine_row(eng, results),
        "per_priority": {
            cls: {"requests": len(rs), **_latency_stats(rs)}
            for cls, rs in per_cls.items()},
    }


def run_mixed(params, cfg, tok, queries, arrivals, args, *, groups=None,
              label="mixed", paged=False, n_pages=None):
    """In-flight mode mixing: one StreamingEngine session serves several
    modes' traffic concurrently through per-mode slot groups sharing one
    cache. Reports overall AND per-mode req/s + latency (the per-mode
    numbers are what the CI bench gate tracks). The paged-oversubscription
    demo reuses this harness with its own ``groups`` + an undersized
    ``n_pages`` pool."""
    groups = groups or {"greedy": args.slots, "speculative": args.slots,
                        "beam": max(1, args.slots // 2)}
    ecfg = EngineConfig(mode="speculative", mode_groups=groups,
                        draft_len=args.draft_len, n_drafts=args.n_drafts,
                        n_beams=args.n_beams, max_new=args.max_new,
                        max_src=96, paged=paged,
                        page_size=args.page_size, n_pages=n_pages)
    eng = StreamingEngine(params, cfg, tok, ecfg)
    names = list(groups)
    modes = [names[i % len(names)] for i in range(len(queries))]
    # warmup: one trace per group step + admit, on a throwaway session
    for m in names:
        eng.submit(queries[0], mode=m)
    eng.serve()
    eng.reset()
    traces0 = dict(eng.n_traces)

    for q, t, m in zip(queries, arrivals, modes):
        eng.submit(q, arrival=float(t), mode=m)
    results = list(eng.serve(realtime=True).values())
    assert dict(eng.n_traces) == traces0, \
        f"mixed traffic retraced after warmup: {traces0} -> {eng.n_traces}"
    if paged:
        eng.allocator.check()

    makespan = max(r.completed for r in results)
    per_mode = {}
    for m in names:
        rs = [r for r in results if r.mode == m]
        per_mode[m] = {
            "requests": len(rs),
            "rps": len(rs) / makespan,
            **_latency_stats(rs),
        }
    return {
        "mode": label,
        "groups": {m: int(n) for m, n in groups.items()},
        "rps": len(results) / makespan,
        **_latency_stats(results),
        "steps": eng.scheduler.n_steps,
        "n_slots": eng.n_slots,
        "slots_resident": eng.scheduler.max_resident,
        "preemptions": eng.scheduler.n_preemptions,
        "per_mode": per_mode,
        "cache": eng.cache_footprint(),
        **_loop_row(eng, results),
    }


def run_sharded(params, cfg, tok, queries, arrivals, args):
    """Mesh-sharded serving: the speculative paged workload on an engine
    partitioned over a (data=2, model=2) mesh — each data shard owns a
    disjoint slot group segment and page-pool segment, parameters shard
    over the model axis, and the steady state stays at ONE donated jitted
    dispatch per scheduler iteration (the same megastep contract as the
    single-device modes, now spanning the mesh). On CPU the mesh runs on
    forced host devices, so req/s is NOT a speedup claim — the number the
    gate tracks is the dispatch accounting plus the placement balance:
    admissions per shard and peak page occupancy per shard must stay
    spread (least-loaded placement), and the paged pool splits into equal
    per-shard segments."""
    from repro.launch.mesh import data_shards, make_serving_mesh

    mesh = make_serving_mesh((2, 2))
    n_sh = data_shards(mesh)
    slots = n_sh * (-(-args.slots // n_sh))   # round up to divide shards
    ecfg = EngineConfig(mode="speculative", draft_len=args.draft_len,
                        n_drafts=args.n_drafts, max_new=args.max_new,
                        max_src=96, n_slots=slots, paged=True,
                        page_size=args.page_size, mesh=mesh)
    eng = StreamingEngine(params, cfg, tok, ecfg)
    _warmup(eng, queries[0])
    traces0 = dict(eng.n_traces)

    for q, t in zip(queries, arrivals):
        eng.submit(q, arrival=float(t))
    results = list(eng.serve(realtime=True).values())
    assert dict(eng.n_traces) == traces0, \
        f"sharded traffic retraced after warmup: {traces0} -> {eng.n_traces}"
    eng.allocator.check()

    st = eng.shard_stats()
    peaks = st["peak_pages_by_shard"]
    caps = st["shard_capacity"]
    mean_peak = sum(peaks) / max(1, len(peaks))
    st["page_balance"] = (max(peaks) / mean_peak) if mean_peak else 1.0
    st["shard_occupancy"] = [p / c for p, c in zip(peaks, caps)]
    return {
        "mode": "sharded",
        "mesh": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        **_engine_row(eng, results),
        **st,
    }


def run_decoder_mode(mode: str, args):
    """Decoder-only serving (DecoderOnlyBackend): ragged random-token
    prompts admitted by chunked prefill, prompt-lookup drafts, same
    Poisson open loop and reporting as the seq2seq modes."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as tr

    cfg = get_config(DECODER_ARCH, reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(mode=mode.removeprefix("decoder_"),
                        draft_len=args.draft_len, n_drafts=args.n_drafts,
                        max_new=args.max_new, max_src=48,
                        n_slots=args.slots, prefill_chunk=16,
                        eos_id=DECODER_EOS)
    eng = StreamingEngine(params, cfg, None, ecfg)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(4, cfg.vocab_size,
                            size=int(rng.integers(8, 48))).astype(np.int32)
               for _ in range(args.requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    _warmup(eng, prompts[0])   # compiles step + admit/chunk/finish once
    traces0 = dict(eng.n_traces)

    for p, t in zip(prompts, arrivals):
        eng.submit(p, arrival=float(t))
    results = list(eng.serve(realtime=True).values())
    assert dict(eng.n_traces) == traces0, \
        f"ragged decoder traffic retraced: {traces0} -> {eng.n_traces}"
    return {"mode": mode, "arch": cfg.name, **_engine_row(eng, results)}


def run_planning(args):
    """Retro*-style planning loop: a search tree of requests where every
    expansion extends its parent's prompt (``submit_child``), served on
    the decoder-only backend with cross-request prefix page sharing. The
    planner reads each node's result before branching (as a best-first
    search would), so parents' committed pages are in the radix cache by
    the time their children are matched. A second, prefix_cache=False
    pass over the SAME tree is the cold control — the shared run must
    allocate strictly fewer pages per request and keep the megastep at
    one dispatch per iteration with zero recompiles."""
    import time

    import jax

    from repro.configs import get_config
    from repro.models import transformer as tr

    cfg = get_config(DECODER_ARCH, reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    branch, depth, suffix_len = 2, 2, 16

    def build_engine(share: bool) -> StreamingEngine:
        ecfg = EngineConfig(mode="greedy", max_new=args.max_new,
                            max_src=96, n_slots=args.slots,
                            prefill_chunk=16, eos_id=DECODER_EOS,
                            paged=True, page_size=args.page_size,
                            prefix_cache=share)
        return StreamingEngine(params, cfg, None, ecfg)

    def expand(eng, rng):
        """One expansion wave: root -> ``branch`` children per finished
        node, ``depth`` levels deep. Returns every node's SlotResult."""
        root = rng.integers(4, cfg.vocab_size, size=33).astype(np.int32)
        frontier = [eng.submit(root)]
        results = []
        for _ in range(depth):
            grown = []
            for h in frontier:
                results.append(h.result())   # read before branching
                for _ in range(branch):
                    sfx = rng.integers(4, cfg.vocab_size,
                                       size=suffix_len).astype(np.int32)
                    grown.append(h.submit_child(sfx))
            frontier = grown
        results.extend(h.result() for h in frontier)
        return results

    eng = build_engine(True)
    expand(eng, np.random.default_rng(args.seed + 1))   # warmup tree
    eng.reset()
    traces0 = dict(eng.n_traces)

    t0 = time.perf_counter()
    results = expand(eng, np.random.default_rng(args.seed))
    elapsed = time.perf_counter() - t0
    assert dict(eng.n_traces) == traces0, \
        f"shared-prefix planning traffic retraced: {traces0} -> {eng.n_traces}"
    stats = eng.prefix_stats()
    eng.allocator.check()

    cold = build_engine(False)
    _warmup(cold, np.random.default_rng(args.seed).integers(
        4, cfg.vocab_size, size=33).astype(np.int32))
    expand(cold, np.random.default_rng(args.seed))
    cold_ppr = cold.prefix_stats()["pages_per_request"]
    assert stats["pages_per_request"] < cold_ppr, \
        (f"prefix sharing must allocate strictly fewer pages/request: "
         f"shared {stats['pages_per_request']:.2f} vs cold {cold_ppr:.2f}")

    return {
        "mode": "planning",
        "arch": cfg.name,
        "rps": len(results) / elapsed,          # routes (tree nodes) / sec
        "requests": len(results),
        "tree": {"branch": branch, "depth": depth,
                 "suffix_len": suffix_len},
        "prefix_hit_rate": stats["prefix_hit_rate"],
        "hit_tokens": stats["hit_tokens"],
        "lookup_tokens": stats["lookup_tokens"],
        "radix_nodes": stats["nodes"],
        "pages_per_request": stats["pages_per_request"],
        "pages_per_request_cold": cold_ppr,
        "n_slots": eng.n_slots,
        "slots_resident": eng.scheduler.max_resident,
        "preemptions": eng.scheduler.n_preemptions,
        "steps": eng.scheduler.n_steps,
        "cache": eng.cache_footprint(),
        **_loop_row(eng, results),
    }


def run_overload(args):
    """Overload replay: the ``benchmarks/load_gen.py`` trace — Poisson
    BURSTS of arrivals, heavy-tailed prompt lengths, mid-stream cancels,
    a deadline-carrying high class over a best-effort low class — served
    by the decoder-only backend with the full overload policy on
    (priority aging + deadline-aware preemption + load shedding). Runs on
    the CLOSED-LOOP step clock, so every reported number is
    deterministic: per-class SLO attainment, shed rate, and the
    best-effort starvation bound join the CI bench gate
    (``--slo-threshold`` / ``--shed-threshold``), and the dispatch
    accounting proves the policy machinery keeps the steady state at one
    megastep per iteration."""
    import jax

    from benchmarks.load_gen import make_trace, prompt_tokens, replay, \
        summarize
    from repro.configs import get_config
    from repro.models import transformer as tr

    cfg = get_config(DECODER_ARCH, reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    policy = OverloadPolicy(aging_rate=0.02,
                            shed_depth=max(6, 3 * args.slots),
                            deadline_preemption=True,
                            preempt_slack_margin=4.0)
    ecfg = EngineConfig(mode="greedy", max_new=args.max_new, max_src=64,
                        n_slots=args.slots, prefill_chunk=16,
                        eos_id=DECODER_EOS, overload=policy)
    eng = StreamingEngine(params, cfg, None, ecfg)
    trace = make_trace(n=max(32, 6 * args.requests), seed=args.seed,
                       prompt_max=56, max_new=args.max_new)
    _warmup(eng, prompt_tokens(trace, 0, cfg.vocab_size))
    traces0 = dict(eng.n_traces)

    handles = replay(eng, trace,
                     lambda t, i: prompt_tokens(trace, i, cfg.vocab_size))
    assert dict(eng.n_traces) == traces0, \
        f"overload traffic retraced after warmup: {traces0} -> {eng.n_traces}"
    metrics = summarize(eng, handles)
    finished = [eng._done[rid] for rid in handles
                if eng._done[rid].status == "finished"]
    makespan = max(r.completed for r in finished)
    return {
        "mode": "overload", "arch": cfg.name,
        "rps": len(finished) / makespan,    # finished per step (closed loop)
        **_latency_stats(finished),
        **metrics,
        "steps": eng.scheduler.n_steps,
        "n_slots": eng.n_slots,
        "slots_resident": eng.scheduler.max_resident,
        "preemptions": eng.scheduler.n_preemptions,
        "n_expired": eng.scheduler.n_expired,
        "n_cancelled": eng.scheduler.n_cancelled,
        "policy": {"aging_rate": policy.aging_rate,
                   "shed_depth": policy.shed_depth,
                   "deadline_preemption": policy.deadline_preemption,
                   "preempt_slack_margin": policy.preempt_slack_margin},
        "cache": eng.cache_footprint(),
        **_loop_row(eng, finished),
    }


def run_fleet(args):
    """Fleet-layer benchmark: real replica subprocesses behind a
    ``FleetRouter``, measured over the wire (loopback SSE), in three
    phases.

    1) capacity, 1 replica: a concurrent request wave through the router
       (best-of-``reps`` makespan — the router overhead is part of the
       measurement, so the 2-replica ratio is an honest router number);
    2) capacity, 2 replicas: the same wave, fresh router. On a host with
       >= 2 usable cores the aggregate must reach 1.5x the single-replica
       number (the fleet's reason to exist); on a single-core host two
       CPU-bound replicas time-slice one CPU, so the scaling assert
       relaxes to a sanity floor and the measured ratio is reported
       alongside ``host_cpus`` for the record;
    3) replica-kill drill, fresh 2-replica fleet with 1-slot/long-decode
       replicas: a seed request homes a prefix family on one replica,
       a backlog of affine requests queues behind a long resident stream,
       and the serving replica is SIGKILLed mid-backlog. Every queued
       request must fail over and FINISH on the survivor (deterministic
       replicas make the tokens identical), streams that had already
       delivered deltas must surface the typed retryable LOST status, and
       every stream sees exactly one ``accepted`` and one terminal event
       — ``reroute_success_rate`` (reroutes that finished / reroutes) is
       the number the CI gate pins at 1.0 (``--reroute-threshold``)."""
    import threading
    import time

    from repro.data import SyntheticReactionDataset
    from repro.serving import FleetConfig, FleetRouter
    from repro.serving.fleet import spawn_replicas, stop_replicas
    from repro.serving.server import sse_events

    ds = SyntheticReactionDataset(16, seed=0)
    n_wave = max(12, args.requests)
    # 6 query families, repeated: the repeats exercise the router's
    # prefix-affine placement across waves (families home after their
    # first completion)
    queries = [ds.pair(i % 6)[0] for i in range(n_wave)]
    rep_args = ["--model", "synthetic", "--mode", "greedy",
                "--slots", str(args.slots), "--max-new", str(args.max_new)]

    def wave(port, qs):
        """One concurrent wave: every query in its own thread; returns
        (makespan, per-request wall latencies)."""
        lat = [0.0] * len(qs)
        bad = []

        def worker(i):
            t0 = time.perf_counter()
            evs = sse_events("127.0.0.1", port, {"query": qs[i]},
                             timeout=300.0)
            lat[i] = time.perf_counter() - t0
            if not evs or evs[-1].get("status") != "finished":
                bad.append((i, evs[-1:]))

        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(len(qs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not bad, f"fleet wave requests failed: {bad}"
        return time.perf_counter() - t0, lat

    def capacity(n_replicas, reps=3):
        """Best-of-``reps`` wave throughput through a fresh
        ``n_replicas``-wide fleet; returns (rps, latencies, router stats)."""
        procs, addrs = spawn_replicas(n_replicas, extra_args=rep_args)
        router = FleetRouter(addrs, FleetConfig(probe_interval_s=0.1))
        router.start()
        try:
            wave(router.port, queries[:2])   # warm the wire path
            best = None
            for _ in range(reps):
                mk, lat = wave(router.port, queries)
                if best is None or mk < best[0]:
                    best = (mk, lat)
            return len(queries) / best[0], best[1], router.stats()
        finally:
            router.shutdown()
            stop_replicas(procs)

    rps_single, _, _ = capacity(1)
    rps_fleet, lats, fstats = capacity(2)
    lat = np.sort(lats)
    speedup = rps_fleet / rps_single
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        cpus = os.cpu_count() or 1
    if cpus >= 2:
        assert speedup >= 1.5, (
            f"2-replica fleet must scale on a {cpus}-core host: "
            f"{speedup:.2f}x < 1.5x")
    else:
        # two CPU-bound replica processes on one core can only time-slice
        # it: parity (minus router overhead) is the physical ceiling, so
        # only a collapse below it is a bug
        assert speedup >= 0.5, (
            f"single-core fleet fell past time-slicing parity: "
            f"{speedup:.2f}x < 0.5x")

    # ---- phase 3: the replica-kill drill --------------------------------
    drill_args = ["--model", "synthetic", "--mode", "greedy",
                  "--slots", "1", "--max-new", "160"]
    n_drill = 7
    procs, addrs = spawn_replicas(2, extra_args=drill_args)
    router = FleetRouter(addrs, FleetConfig(probe_interval_s=0.1))
    router.start()
    try:
        q = ds.pair(13)[0]
        seed = sse_events("127.0.0.1", router.port, {"query": q},
                          timeout=300.0)
        assert seed[-1].get("status") == "finished", seed[-1:]
        target = next(e for e in seed
                      if e.get("event") == "accepted")["replica"]
        outs: list = [None] * n_drill
        ts = [threading.Thread(
            target=lambda i=i: outs.__setitem__(i, sse_events(
                "127.0.0.1", router.port, {"query": q}, timeout=300.0)))
            for i in range(n_drill)]
        for t in ts:
            t.start()
        # ~0.17s decode per request on a 1-slot replica leaves a >1s
        # backlog window; kill lands mid-backlog
        time.sleep(0.35)
        procs[target].kill()
        for t in ts:
            t.join()
        st = router.stats()
    finally:
        router.shutdown()
        stop_replicas(procs)

    drill_lost = 0
    for i, evs in enumerate(outs):
        accs = [e for e in evs if e.get("event") == "accepted"]
        terms = [e for e in evs if e.get("event") == "rejected"
                 or (e.get("event") == "done" and "status" in e)]
        assert len(accs) == 1 and len(terms) == 1, \
            f"drill stream {i} must see exactly one accept + one terminal"
        term = terms[0]
        if term.get("status") == "finished":
            continue
        assert (term.get("status") == "lost" and term.get("retryable")
                and term.get("retry_after", 0) > 0), \
            f"drill stream {i} ended untyped: {term}"
        drill_lost += 1
    rerouted, reroute_ok = st["rerouted"], st["reroute_ok"]
    assert rerouted >= 1, "kill drill produced no reroutes — no backlog " \
        "was in flight when the replica died"
    rate = reroute_ok / rerouted if rerouted else 0.0
    assert rate == 1.0 and st["lost"] == drill_lost, (
        f"every queued request must fail over and finish: "
        f"{reroute_ok}/{rerouted} rerouted ok, router lost {st['lost']} "
        f"vs streams lost {drill_lost}")

    return {
        "mode": "fleet",
        "replicas": 2,
        "requests": n_wave,
        "rps": rps_fleet,
        "rps_single": rps_single,
        "fleet_speedup": speedup,
        "host_cpus": cpus,
        "p50": float(np.percentile(lat, 50)),
        "p95": float(np.percentile(lat, 95)),
        "p99": float(np.percentile(lat, 99)),
        "router_prefix_hit_rate": fstats["prefix_hit_rate"],
        "drill_requests": n_drill,
        "reroute_count": rerouted,
        "reroute_success_rate": rate,
        "drill_lost": drill_lost,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (req/s); default saturates "
                         "the slots so req/s measures capacity")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--draft-len", type=int, default=16)
    # the CPU host pays per draft row, so the default keeps one long draft;
    # on accelerators raise toward the paper's N_d ~ 25 (parallel slack)
    ap.add_argument("--n-drafts", type=int, default=1)
    ap.add_argument("--n-beams", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--modes", nargs="*", default=list(MODES))
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--no-paged-demo", action="store_true",
                    help="skip the oversubscribed paged-cache pass")
    args = ap.parse_args()
    enable_compile_cache()

    cfg, params, train_ds, test_ds = trained_model(verbose=True,
                                                   direction="retro")
    tok = train_ds.tokenizer
    rng = np.random.default_rng(args.seed)
    queries = [test_ds.pair(i % 48)[0] for i in range(args.requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))

    print(f"\n{args.requests} requests, Poisson rate {args.rate}/s, "
          f"{args.slots} slots, max_new={args.max_new}")
    print(f"{'mode':18s} {'req/s':>7s} {'p50 lat':>9s} {'p95 lat':>9s} "
          f"{'steps':>6s} {'accept':>7s} {'disp/tok':>9s} {'gap p95':>9s}")
    rows = {}
    for mode in args.modes:
        if mode == "mixed":
            r = run_mixed(params, cfg, tok, queries, arrivals, args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
                  f"{r['p95']:8.2f}s {r['steps']:6d} {'':>7s} "
                  f"{r['dispatches_per_token']:9.2f} "
                  f"{r['step_gap_p95_s'] * 1e3:7.1f}ms")
            for m, pm in r["per_mode"].items():
                print(f"  mixed/{m:11s} {pm['rps']:7.2f} {pm['p50']:8.2f}s "
                      f"{pm['p95']:8.2f}s {pm['requests']:5d}r")
            continue
        if mode == "priority_mix":
            r = run_priority_mix(params, cfg, tok, queries, arrivals, args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
                  f"{r['p95']:8.2f}s {r['steps']:6d} {'':>7s} "
                  f"{r['dispatches_per_token']:9.2f} "
                  f"{r['step_gap_p95_s'] * 1e3:7.1f}ms")
            for cls, pc in r["per_priority"].items():
                print(f"  prio/{cls:12s} queue delay p50 "
                      f"{pc['queue_delay_p50']:6.2f}s  p95 "
                      f"{pc['queue_delay_p95']:6.2f}s  {pc['requests']:3d}r")
            continue
        if mode == "planning":
            r = run_planning(args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} routes/s  "
                  f"hit rate {r['prefix_hit_rate']:5.2f}  "
                  f"pages/req {r['pages_per_request']:5.2f} "
                  f"(cold {r['pages_per_request_cold']:5.2f})  "
                  f"{r['dispatches_per_token']:5.2f} d/tok")
            continue
        if mode == "overload":
            r = run_overload(args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
                  f"{r['p95']:8.2f}s {r['steps']:6d} "
                  f"slo_hi {r['slo_high']:4.2f} slo_lo {r['slo_low']:4.2f} "
                  f"shed {r['shed_rate']:4.2f} "
                  f"starve<= {r['starvation_bound']:5.1f} "
                  f"preempt {r['preemptions']:2d}")
            continue
        if mode == "fleet":
            r = run_fleet(args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
                  f"{r['p95']:8.2f}s {'':>6s} {'':>7s} "
                  f"p99 {r['p99']:5.2f}s")
            print(f"  1 replica {r['rps_single']:6.2f} req/s -> "
                  f"{r['replicas']} replicas {r['rps']:6.2f} req/s "
                  f"({r['fleet_speedup']:.2f}x on {r['host_cpus']} "
                  f"core(s))  affinity hit rate "
                  f"{r['router_prefix_hit_rate']:.2f}")
            print(f"  kill drill: {r['drill_requests']} in flight, "
                  f"{r['reroute_count']} rerouted "
                  f"(success {r['reroute_success_rate']:.2f}), "
                  f"{r['drill_lost']} lost (typed retryable)")
            continue
        if mode == "sharded":
            r = run_sharded(params, cfg, tok, queries, arrivals, args)
            rows[mode] = r
            print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
                  f"{r['p95']:8.2f}s {r['steps']:6d} {r['acceptance']:7.2f} "
                  f"{r['dispatches_per_token']:9.2f} "
                  f"{r['step_gap_p95_s'] * 1e3:7.1f}ms")
            occ = " ".join(f"{o:.2f}" for o in r["shard_occupancy"])
            print(f"  mesh {r['mesh']} admits {r['admitted_by_shard']} "
                  f"(imbalance {r['admit_imbalance']:.2f})  "
                  f"peak pages {r['peak_pages_by_shard']} "
                  f"(balance {r['page_balance']:.2f})  occupancy {occ}")
            continue
        if mode.startswith("decoder_"):
            r = run_decoder_mode(mode, args)
        else:
            r = run_mode(mode, params, cfg, tok, queries, arrivals, args)
        rows[mode] = r
        print(f"{r['mode']:18s} {r['rps']:7.2f} {r['p50']:8.2f}s "
              f"{r['p95']:8.2f}s {r['steps']:6d} {r['acceptance']:7.2f} "
              f"{r['dispatches_per_token']:9.2f} "
              f"{r['step_gap_p95_s'] * 1e3:7.1f}ms")

    if "greedy" in rows and "speculative" in rows:
        speedup = rows["speculative"]["rps"] / rows["greedy"]["rps"]
        print(f"\nspeculative vs greedy throughput at {args.slots} slots: "
              f"{speedup:.2f}x")
    if "beam" in rows and "speculative_beam" in rows:
        speedup = rows["speculative_beam"]["rps"] / rows["beam"]["rps"]
        print(f"speculative beam vs beam throughput:  {speedup:.2f}x")

    paged_demo = None
    if not args.no_paged_demo:
        # MIXED paged oversubscription: one session, greedy + speculative
        # slot groups fighting over ONE page pool sized to ~1.5 primary
        # slots' worst case while serving 2x the slot count per group —
        # the resident-slot high-water mark exceeds what contiguous rows
        # would fit in the same HBM (the paged cache's acceptance
        # criterion), now across mode groups
        demo_slots = 2 * args.slots
        groups = {"greedy": demo_slots, "speculative": demo_slots}
        _, K, N_d, DL = _mode_shape(EngineConfig(
            mode="speculative", draft_len=args.draft_len,
            n_drafts=args.n_drafts, n_beams=args.n_beams))
        spec = SessionSpec(n_slots=demo_slots, n_beams=K, n_drafts=N_d,
                           draft_len=DL, max_new=args.max_new, eos_id=0,
                           kind="greedy")
        blocks_per_slot = (spec.rows_per_slot
                           * (-(-spec.cache_len // args.page_size)))
        n_pages = 1 + blocks_per_slot + blocks_per_slot // 2
        paged_demo = run_mixed(params, cfg, tok, queries, arrivals, args,
                               groups=groups, label="mixed_paged",
                               paged=True, n_pages=n_pages)
        fp = paged_demo["cache"]
        n_slots = paged_demo["n_slots"]
        print(f"\npaged demo (mixed greedy+speculative): {n_slots} "
              f"slots on a pool worth {fp['contiguous_equiv_slots']} "
              f"contiguous slot(s) — "
              f"{paged_demo['slots_resident']} resident at peak, "
              f"{paged_demo['preemptions']} preemption(s), "
              f"peak cache {fp['peak_bytes'] / 1024:.0f} KiB "
              f"/ cap {fp['capacity_bytes'] / 1024:.0f} KiB, "
              f"{paged_demo['rps']:.2f} req/s")
        # the criterion: the session legitimately runs with more slots than
        # the same HBM could hold as contiguous rows (co-residency above the
        # contiguous bound additionally shows up in slots_resident whenever
        # requests underrun their worst case, as in the committed run)
        assert paged_demo["n_slots"] > fp["contiguous_equiv_slots"], \
            "paged demo pool must undercut the contiguous-row HBM budget"

    if args.json:
        payload = {
            "benchmark": "serving_throughput",
            "config": {k: getattr(args, k) for k in
                       ("requests", "rate", "slots", "max_new", "draft_len",
                        "n_drafts", "n_beams", "page_size", "seed")},
            "modes": rows,
            "paged_demo": paged_demo,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
