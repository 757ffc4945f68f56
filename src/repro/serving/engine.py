"""Serving engines: the industrial-application layer the paper targets
(reaction-prediction assistants, CASP single-step retrosynthesis models).

Pipeline per request:
  tokenize -> encode once -> extract source-copy drafts (host, vectorized)
  -> speculative greedy / speculative beam search -> detokenize.

Decoding modes mirror the paper's experiments:
  greedy               Table 2 baseline
  speculative          Table 2, DL/N_d configurable
  beam                 Table 3/4 baseline
  speculative_beam     Table 3/4, the paper's SBS

Two engines share these modes:

``ReactionEngine`` — the per-request reference: jits one closed decode
loop per (mode, batch-shape) and runs each request batch to completion.
Every request waits for the slowest member of its batch.

``StreamingEngine`` — the production path: a ``DecodeSession`` with S
fixed slots driven by ``repro.serving.scheduler.ContinuousScheduler``.
ONE jitted step + ONE jitted admit per slot group serve every request
forever (slot index is traced, so admissions into freed slots never
recompile), beams are batched across slots (no B=1 restriction), and
finished sequences leave immediately. Outputs are token-identical to
``ReactionEngine`` — ``tests/test_session.py`` verifies all four modes.

Architecture-agnostic serving: everything model-specific — cache
construction, the step handle, and how a request's context enters its
slot's cache rows — lives behind a ``ModelBackend``
(``repro.serving.backend``). ``Seq2SeqBackend`` keeps the Molecular
Transformer path token-identical (encode + cross-K/V scatter in one
jitted admit); ``DecoderOnlyBackend`` serves every decoder-only family
(dense GQA, MoE, SSM/hybrid) with prompt-lookup drafting and **chunked
ragged prefill**: long prompts enter the slot's cache rows in fixed-size
chunks interleaved with decode steps — through the slot's block table
when the cache is paged — so resident requests never stall behind a new
admission, and a ragged stream of prompt lengths never retraces
(``tests/test_backend.py``).

In-flight mode mixing: ``EngineConfig.mode_groups`` partitions the slot
axis into per-mode slot groups — e.g. greedy×4, speculative×4, beam×2 —
that share one model cache (one paged page pool, one ``PageAllocator``)
and one jitted step (``repro.core.session.grouped_step``). A production
retrosynthesis planner can then issue cheap greedy forward-prediction
probes and expensive beam expansions against the same session: requests
are tagged with a mode at ``submit()`` and route to their group's slots,
admitting one mode never retraces another group, and page-gated
admission/preemption arbitrate the shared pool across all groups.
``tests/test_mixed_mode.py`` verifies every request in a mixed session is
token-identical to the corresponding single-mode engine run.

Request front door (``repro.serving.api``): ``submit()`` returns a
``RequestHandle`` (an ``int`` — the request id — so legacy
``{rid: SlotResult}`` flows are untouched) and accepts per-request
``GenerationParams`` (validated against the group's compile-shape
ceilings; ragged values ride in device arrays, changing zero traced
shapes), a ``priority``, and a ``deadline``. ``serve_steps()`` is the
step-driven generator the blocking ``serve()`` wraps; between iterations
it feeds committed-token deltas to any ``handle.stream()`` consumers.
``handle.cancel()`` dequeues a queued request or evicts a resident one
mid-flight, reclaiming its pages. ``predict``/``predict_topn`` are thin
compatibility wrappers over this surface.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import (
    batch_drafts, beam_search, extract_drafts, greedy_decode, seq2seq_handle,
    speculative_beam_search, speculative_greedy_decode,
)
from repro.core.session import (GroupedState, PageAllocator, PoolExhausted,
                                RadixPageCache, SessionSpec,
                                ShardedPageAllocator, alias_prefix_pages,
                                apply_page_plan, clear_index_cells,
                                device_free_pages, device_free_pages_by_shard,
                                device_page_plan, grouped_init_state,
                                grouped_step, radix_cell_coords,
                                read_row_pages, release_slot, reset_slot,
                                unmap_cache_rows, write_index_cells)
from repro.data.tokenizer import SmilesTokenizer
from repro.launch.mesh import data_shards
from repro.launch.shardings import (serving_param_shardings,
                                    serving_state_shardings)
from repro.models import seq2seq as s2s
from repro.serving.api import (MAX_STOP_IDS, GenerationParams,
                               RequestCancelled, RequestHandle,
                               RequestRejected, RequestSpec, RequestStatus)
from repro.serving.backend import make_backend
from repro.serving.scheduler import (ContinuousScheduler, OverloadPolicy,
                                     SlotResult)


@dataclasses.dataclass
class EngineConfig:
    mode: str = "speculative"        # greedy|speculative|beam|speculative_beam
    draft_len: int = 10              # the paper's best DL
    n_drafts: int = 25               # the paper's N_d cap
    n_beams: int = 5
    max_new: int = 96
    max_src: int = 128
    dilations: tuple[int, ...] = (1,)
    n_slots: int = 2                 # StreamingEngine decode slots
    # in-flight mode mixing (StreamingEngine): partition the slot axis into
    # per-mode slot groups sharing one cache/pool/step, e.g.
    # {"greedy": 4, "speculative": 4, "beam": 2}. None = one group of
    # ``mode`` × ``n_slots`` (the classic single-mode session).
    mode_groups: dict[str, int] | tuple | None = None
    # paged KV cache (StreamingEngine): HBM scales with live tokens, not
    # n_slots * worst case — admission is gated on free pages and n_slots
    # may exceed what contiguous rows would fit in the same budget
    paged: bool = False
    page_size: int = 16              # tokens per page
    n_pages: int | None = None       # pool size; None = worst case (no
                                     # oversubscription, paged layout only)
    # model backend: "auto" routes on cfg.family (seq2seq -> monolithic
    # admission, anything else -> decoder-only chunked prefill)
    backend: str = "auto"
    # chunked ragged prefill (decoder-only): tokens written per scheduler
    # iteration while a prompt streams into its slot's cache rows
    prefill_chunk: int = 32
    # decoder-only sessions have no chemistry tokenizer: special ids come
    # from here when StreamingEngine is built with tokenizer=None
    eos_id: int | None = None
    pad_id: int = 0
    # cross-request prefix page sharing (the planning-search workload):
    # decoder-only paged engines keep a radix tree over committed prompt
    # pages and admit by aliasing matched pages, prefilling only the
    # unmatched suffix; seq2seq engines reuse the encoder output for
    # repeated sources instead (the whole source is the "prefix" there).
    # Off by default — sharing never changes tokens, but the index rows it
    # reserves change cache shapes, so it is opt-in per engine.
    prefix_cache: bool = False
    # retained-page capacity of the radix cache (index cells). None =
    # 2 * n_slots * worst-case prompt blocks.
    prefix_cache_pages: int | None = None
    # seq2seq encoder-output reuse: LRU entries kept (each caches one
    # source's cross-attention K/V + mask)
    prefix_cache_entries: int = 128
    # overload policy (StreamingEngine scheduler): priority aging,
    # deadline-aware preemption, load shedding with retry-after. None =
    # everything off (strict priority/EDF/FIFO, unbounded queues).
    overload: OverloadPolicy | None = None
    # sharded serving (StreamingEngine): a jax.sharding.Mesh with a
    # ("data", "model") axis pair. Slot axes, the paged page pool, and
    # the admission/preemption accounting partition across the data axis
    # (each data shard owns a disjoint slot group and page-pool segment);
    # params shard across "model" via sharding/rules.py. The megastep
    # stays ONE donated dispatch spanning all devices, and tokens are
    # identical to the single-device engine. None = single device.
    mesh: object | None = None

    def __post_init__(self):
        """Fail at construction, not as a deep shape/assert error later."""
        for name, lo in (("max_new", 1), ("max_src", 1), ("draft_len", 0),
                         ("n_drafts", 1), ("n_beams", 1), ("n_slots", 1),
                         ("prefill_chunk", 1), ("page_size", 1)):
            if getattr(self, name) < lo:
                raise ValueError(f"EngineConfig.{name}={getattr(self, name)} "
                                 f"must be >= {lo}")
        if self.prefix_cache_pages is not None and self.prefix_cache_pages < 1:
            raise ValueError(
                f"EngineConfig.prefix_cache_pages={self.prefix_cache_pages} "
                f"must be >= 1 (it is the radix cache's retained-page "
                f"capacity)")
        if self.prefix_cache_entries < 1:
            raise ValueError(
                f"EngineConfig.prefix_cache_entries="
                f"{self.prefix_cache_entries} must be >= 1")
        if self.n_pages is not None and self.n_pages < 2:
            raise ValueError(
                f"EngineConfig.n_pages={self.n_pages}: a paged pool needs at "
                f"least the reserved trash page plus one usable page "
                f"(PageAllocator additionally validates the pool against one "
                f"slot's worst case)")
        modes = (dict(self.mode_groups) if self.mode_groups
                 else {self.mode: self.n_slots})
        for mode, n in modes.items():
            if mode not in ("greedy", "speculative", "beam",
                            "speculative_beam"):
                raise ValueError(f"unknown decode mode {mode!r}")
            if int(n) < 1:
                raise ValueError(f"mode group {mode!r} needs >= 1 slot, "
                                 f"got {n}")


@dataclasses.dataclass
class Prediction:
    smiles: list[str]                # candidates, best first
    logprobs: list[float]
    n_calls: int
    acceptance_rate: float
    wall_s: float


def _mode_shape(ecfg: EngineConfig,
                mode: str | None = None) -> tuple[str, int, int, int]:
    """mode -> (session kind, beams K, drafts N_d, draft length DL)."""
    return {
        "greedy": ("greedy", 1, 1, 0),
        "speculative": ("greedy", 1, ecfg.n_drafts, ecfg.draft_len),
        "beam": ("beam", ecfg.n_beams, 1, 0),
        "speculative_beam": ("beam", ecfg.n_beams, ecfg.n_drafts,
                             ecfg.draft_len),
    }[ecfg.mode if mode is None else mode]


class ReactionEngine:
    """Per-request reference engine (one jitted closed loop per batch)."""

    def __init__(self, params, cfg: ModelConfig, tokenizer: SmilesTokenizer,
                 engine_cfg: EngineConfig | None = None):
        self.params = params
        self.cfg = cfg
        self.tok = tokenizer
        self.ecfg = engine_cfg or EngineConfig()
        self._jitted: dict = {}

    # -- jitted inner functions (cached per batch-shape) --------------------
    def _greedy_fn(self, B):
        ecfg = self.ecfg

        @jax.jit
        def run(params, src):
            memory, src_mask = s2s.encode(params, self.cfg, src)
            handle = seq2seq_handle(params, self.cfg, memory_mask=src_mask)
            cache = s2s.init_cache(self.cfg, B, ecfg.max_new + 2,
                                   memory=memory, params=params)
            last = jnp.full((B,), self.tok.bos_id, jnp.int32)
            pos = jnp.zeros((B,), jnp.int32)
            return greedy_decode(handle, cache, last, pos,
                                 max_new=ecfg.max_new, eos_id=self.tok.eos_id)

        return run

    def _spec_fn(self, B):
        ecfg = self.ecfg

        @jax.jit
        def run(params, src, drafts, mask):
            memory, src_mask = s2s.encode(params, self.cfg, src)
            handle = seq2seq_handle(params, self.cfg, memory_mask=src_mask)
            cache = s2s.init_cache(self.cfg, B,
                                   ecfg.max_new + ecfg.draft_len + 2,
                                   memory=memory, params=params)
            last = jnp.full((B,), self.tok.bos_id, jnp.int32)
            pos = jnp.zeros((B,), jnp.int32)
            return speculative_greedy_decode(
                handle, cache, last, pos, drafts, mask,
                max_new=ecfg.max_new, eos_id=self.tok.eos_id)

        return run

    def _beam_fn(self, spec: bool):
        ecfg = self.ecfg

        @jax.jit
        def run(params, src, drafts, mask):
            memory, src_mask = s2s.encode(params, self.cfg, src)
            handle = seq2seq_handle(params, self.cfg, memory_mask=src_mask)
            size = ecfg.max_new + (ecfg.draft_len if spec else 0) + 2
            cache = s2s.init_cache(self.cfg, 1, size, memory=memory,
                                   params=params)
            if spec:
                return speculative_beam_search(
                    handle, cache, self.tok.bos_id, 0, drafts, mask,
                    n_beams=ecfg.n_beams, max_new=ecfg.max_new,
                    eos_id=self.tok.eos_id)
            return beam_search(handle, cache, self.tok.bos_id, 0,
                               n_beams=ecfg.n_beams, max_new=ecfg.max_new,
                               eos_id=self.tok.eos_id)

        return run

    def _get(self, kind, *args):
        key = (kind,) + args
        if key not in self._jitted:
            maker = {"greedy": self._greedy_fn, "spec": self._spec_fn,
                     "beam": self._beam_fn}[kind]
            self._jitted[key] = maker(*args)
        return self._jitted[key]

    # -- public API ----------------------------------------------------------
    def _encode_src(self, queries: Sequence[str]) -> np.ndarray:
        rows = [self.tok.encode_padded(q, self.ecfg.max_src, add_eos=True)
                for q in queries]
        return np.stack(rows)

    def predict(self, queries: Sequence[str]) -> list[Prediction]:
        """Batched greedy / speculative-greedy prediction (one best output)."""
        ecfg = self.ecfg
        src = jnp.asarray(self._encode_src(queries))
        B = src.shape[0]
        t0 = time.time()
        if ecfg.mode == "greedy":
            res = self._get("greedy", B)(self.params, src)
            rate = jnp.zeros((B,))
        elif ecfg.mode == "speculative":
            drafts, mask = batch_drafts(np.asarray(src), ecfg.draft_len,
                                        ecfg.n_drafts,
                                        dilations=ecfg.dilations)
            res = self._get("spec", B)(self.params, src, jnp.asarray(drafts),
                                       jnp.asarray(mask))
            rate = res.acceptance_rate
        else:
            raise ValueError(f"predict() supports greedy/speculative, "
                             f"got {ecfg.mode}")
        jax.block_until_ready(res.tokens)
        wall = time.time() - t0
        out = []
        for b in range(B):
            smi = self.tok.decode(np.asarray(res.tokens[b]))
            out.append(Prediction(smiles=[smi], logprobs=[0.0],
                                  n_calls=int(res.n_calls),
                                  acceptance_rate=float(rate[b]),
                                  wall_s=wall / B))
        return out

    def predict_topn(self, query: str) -> Prediction:
        """Beam / speculative-beam search for one query (the paper's B=1
        retrosynthesis serving regime; StreamingEngine lifts it)."""
        ecfg = self.ecfg
        src = jnp.asarray(self._encode_src([query]))
        spec = ecfg.mode == "speculative_beam"
        dl = ecfg.draft_len if spec else 0
        drafts, mask = extract_drafts(np.asarray(src[0]), max(dl, 1),
                                      ecfg.n_drafts, dilations=ecfg.dilations)
        if dl == 0:
            drafts = drafts[:1, :0]
            mask = mask[:1]
        t0 = time.time()
        res = self._get("beam", spec)(self.params, src, jnp.asarray(drafts),
                                      jnp.asarray(mask))
        jax.block_until_ready(res.tokens)
        wall = time.time() - t0
        smiles = [self.tok.decode(np.asarray(res.tokens[i]))
                  for i in range(res.tokens.shape[0])]
        # true rate: committed draft tokens / generated tokens on the best
        # beam's path, same convention as predict()
        accepted = int(getattr(res, "accepted_tokens", 0))
        generated = int(res.lengths[0])
        return Prediction(smiles=smiles,
                          logprobs=[float(x) for x in res.logprobs],
                          n_calls=int(res.n_calls),
                          acceptance_rate=accepted / max(generated, 1),
                          wall_s=wall)


class StreamingEngine:
    """Continuous-batching engine: S decode slots in per-mode slot groups,
    one jitted step, one jitted admit/release per group."""

    def __init__(self, params, cfg: ModelConfig,
                 tokenizer: SmilesTokenizer | None = None,
                 engine_cfg: EngineConfig | None = None, *,
                 backend=None):
        self.params = params
        self.cfg = cfg
        self.tok = tokenizer
        self.ecfg = ecfg = engine_cfg or EngineConfig()
        self.backend = backend or make_backend(cfg, ecfg, tokenizer)
        # sharded serving: n_shards data shards each own a contiguous
        # local-slot range of every group and a contiguous page-pool
        # segment; params shard over the mesh's model axis
        self.mesh = ecfg.mesh
        self.n_shards = data_shards(self.mesh) if self.mesh is not None else 1
        if self.mesh is not None:
            # tensor-parallel only for decode (no FSDP: a per-step
            # all-gather would put the whole parameter footprint on the
            # interconnect every iteration), restricted to layouts that
            # execute exactly — see serving_param_shardings
            self.params = jax.device_put(
                self.params,
                serving_param_shardings(self.params, cfg, self.mesh))
        eos_id = tokenizer.eos_id if tokenizer is not None else ecfg.eos_id
        pad_id = tokenizer.pad_id if tokenizer is not None else ecfg.pad_id
        if eos_id is None:
            raise ValueError(
                "StreamingEngine built with tokenizer=None needs "
                "EngineConfig.eos_id so sequences can terminate")
        group_slots = (dict(ecfg.mode_groups) if ecfg.mode_groups
                       else {ecfg.mode: ecfg.n_slots})
        self._groups: dict[str, SessionSpec] = {}
        for mode, n_slots in group_slots.items():
            kind, K, N_d, DL = _mode_shape(ecfg, mode)
            self._groups[mode] = SessionSpec(
                n_slots=int(n_slots), n_beams=K, n_drafts=N_d, draft_len=DL,
                max_new=ecfg.max_new, eos_id=eos_id,
                pad_id=pad_id, kind=kind, n_stop=MAX_STOP_IDS)
        self.mode_names = list(self._groups)
        self.default_mode = (ecfg.mode if ecfg.mode in self._groups
                             else self.mode_names[0])
        self.spec = self._groups[self.default_mode]   # primary (legacy API)
        # group g owns cache rows [row_lo[g], row_lo[g] + n_rows_g) and
        # global scheduler slots [slot_base[g], slot_base[g] + n_slots_g)
        self._row_lo, self._slot_base, self._slot_map = {}, {}, []
        rows = slots = 0
        for mode, spec in self._groups.items():
            self._row_lo[mode], self._slot_base[mode] = rows, slots
            self._slot_map += [(mode, i) for i in range(spec.n_slots)]
            rows += spec.n_rows
            slots += spec.n_slots
        self.n_rows, self.n_slots = rows, slots
        # per-row cache length: the backend may extend it past the decode
        # window (decoder-only rows also hold the prompt)
        self.cache_len = max(self.backend.row_len(s)
                             for s in self._groups.values())
        # cross-request prefix sharing: a radix tree over committed prompt
        # pages (decoder-only + paged, where prompts live in pages), or an
        # encoder-output LRU (seq2seq, where the source IS the prefix).
        # Retained pages stay allocated through reserved block-table INDEX
        # ROWS appended after the group rows: one (row, block) cell per
        # radix node holds the node's page id, so both page planners see a
        # live reference without any decode lane ever reading the row.
        self._prefix_sharing = bool(ecfg.prefix_cache and ecfg.paged
                                    and self.backend.chunked)
        self._encode_reuse = bool(ecfg.prefix_cache
                                  and not self.backend.chunked)
        self._n_index_rows = self._n_cells = 0
        self.radix: RadixPageCache | None = None
        if self._prefix_sharing:
            ps = ecfg.page_size
            # worst-case prompt pages for one slot (the alias/retain lane pad)
            self._prefix_pad = self.backend.prefill_blocks(ps)
            # prefix matches are truncated to whole multiples of
            # lcm(page_size, prefill_chunk) pages so the suffix prefill
            # lands on the cold run's chunk grid — identical chunk
            # partition => bitwise-identical K/V => token identity
            chunk = max(1, int(ecfg.prefill_chunk))
            self._align_pages = chunk // math.gcd(ps, chunk)
            self._table_blocks = -(-self.cache_len // ps)
            self._n_cells = (ecfg.prefix_cache_pages
                             if ecfg.prefix_cache_pages is not None
                             else 2 * self.n_slots * self._prefix_pad)
            self._n_index_rows = -(-self._n_cells // self._table_blocks)
        # shard maps: global slot -> data shard, cache row -> data shard
        # (index rows stay on shard 0 — their cells only PIN pages, the
        # page planner never allocates for them). Shard s owns local
        # slots [s*per, (s+1)*per) of each group, matching the
        # NamedSharding partition of the slot axis, so a shard's slots,
        # rows, and page segment live on the same devices.
        self._shard_of_slot: dict[int, int] = {}
        self._row_shard: np.ndarray | None = None
        if self.n_shards > 1:
            rs = np.zeros((self.n_rows + self._n_index_rows,), np.int32)
            for mode, spec in self._groups.items():
                if spec.n_slots % self.n_shards:
                    raise ValueError(
                        f"mode group {mode!r}: n_slots={spec.n_slots} must "
                        f"divide evenly over the mesh's {self.n_shards} "
                        f"data shards")
                per = spec.n_slots // self.n_shards
                base, lo = self._slot_base[mode], self._row_lo[mode]
                for i in range(spec.n_slots):
                    sh = i // per
                    self._shard_of_slot[base + i] = sh
                    r0 = lo + i * spec.rows_per_slot
                    rs[r0:r0 + spec.rows_per_slot] = sh
            self._row_shard = rs
        # trace counters (incremented at TRACE time only): after one warmup
        # request per mode, mixed traffic must not grow any of these — the
        # zero-recompilation acceptance criterion tests assert on it
        self.n_traces = {"step": 0}
        self.n_traces.update({("admit", m): 0 for m in self._groups})
        if self.backend.chunked:
            # the fused megastep has a second variant that carries this
            # iteration's prefill chunk lanes (chunked backends only — a
            # monolithic session never prefills inside the step)
            self.n_traces["step_prefill"] = 0
            self.n_traces.update({("finish", m): 0 for m in self._groups})
        if self._prefix_sharing:
            self.n_traces.update(share=0, retain=0, evict_cells=0)
        if self._encode_reuse:
            self.n_traces["encode"] = 0
            self.n_traces.update({("admit_cached", m): 0
                                  for m in self._groups})
        # donate the session state: the scheduler threads it linearly, so
        # XLA updates the (dominant) cache buffers in place every step.
        # ONE dispatch per steady-state iteration: the megastep fuses page
        # maintenance + prefill chunks + the grouped decode step.
        self._megastep_fn = jax.jit(self._megastep_impl,
                                    donate_argnums=(1,))
        if self.backend.chunked:
            self._megastep_prefill_fn = jax.jit(
                self._megastep_prefill_impl, donate_argnums=(1,))
        self._admit_fns = {m: self._make_admit(m) for m in self._groups}
        if self.backend.chunked:
            self._finish_fns = {m: self._make_finish(m) for m in self._groups}
        self._release_fns = {m: self._make_release(m) for m in self._groups}
        if self._prefix_sharing:
            # fixed-lane (prefix_pad-wide) block-table edits, each ONE
            # dispatch: alias a matched chain into an admitted slot's row0,
            # write freshly committed pages into radix index cells, clear
            # evicted cells. Lane counts are data, so each traces once.
            def _alias_impl(gstate, row0, pages, count):
                self.n_traces["share"] += 1
                cache = alias_prefix_pages(gstate.cache, row0, pages, count)
                return GroupedState(groups=gstate.groups, cache=cache)

            def _retain_impl(gstate, rows, blocks, pages, count):
                self.n_traces["retain"] += 1
                cache = write_index_cells(gstate.cache, rows, blocks, pages,
                                          count)
                return GroupedState(groups=gstate.groups, cache=cache)

            def _evict_impl(gstate, rows, blocks, count):
                self.n_traces["evict_cells"] += 1
                cache = clear_index_cells(gstate.cache, rows, blocks, count)
                return GroupedState(groups=gstate.groups, cache=cache)

            self._alias_fn = jax.jit(_alias_impl, donate_argnums=(0,))
            self._retain_fn = jax.jit(_retain_impl, donate_argnums=(0,))
            self._evict_cells_fn = jax.jit(_evict_impl, donate_argnums=(0,))
        if self._encode_reuse:
            def _encode_impl(params, src):
                self.n_traces["encode"] += 1
                return self.backend.encode_kv(params, src)

            self._encode_fn = jax.jit(_encode_impl)
            self._admit_cached_fns = {m: self._make_admit_cached(m)
                                      for m in self._groups}
        # dispatch-ahead loop instrumentation: total jitted dispatches,
        # per-iteration dispatch counts, and host step-gap samples (time
        # between consecutive bundle syncs) — bounded, benchmark-read
        self.n_dispatches = 0
        self._disp_mark = 0
        self._dispatch_samples: list[int] = []
        self._step_gaps: list[float] = []
        self._last_sync_t: float | None = None
        # host-side chunked-prefill bookkeeping: global slot ->
        # {mode, req, next-chunk cursor}; slots currently decoding
        # (admission fully applied)
        self._prefilling: dict[int, dict] = {}
        self._decoding: set[int] = set()
        self.allocator: PageAllocator | None = None
        # request-level front door state: terminal records by rid (the
        # handles' view; reset() drops it), the current serve() epoch's
        # records, live stream cursors/buffers, and the single step pump
        # every blocking call drives
        self._done: dict[int, SlotResult] = {}
        self._epoch: dict[int, SlotResult] = {}
        self._streams: dict[int, dict] = {}
        self._pump = None
        self._pump_realtime = False
        self.scheduler = self._new_scheduler()

    # terminal records kept for RequestHandle.result()/.status after their
    # serve() epoch: bounded so an hours-long session (the search-tree
    # workload) cannot grow without limit — oldest insertions evict first,
    # and an evicted rid reports "unknown" (consume results promptly)
    _DONE_CAP = 4096

    # -- jitted session functions (compiled ONCE per engine group, every
    #    request and every slot of the group reuses them) -------------------
    def _megastep_impl(self, params, gstate):
        """Fused megastep, decode-only variant: page maintenance + ONE
        grouped decode iteration in a single dispatch."""
        self.n_traces["step"] += 1
        return self._megastep_body(params, gstate, None)

    def _megastep_prefill_impl(self, params, gstate, prefill):
        """Fused megastep carrying this iteration's prefill chunk lanes
        (chunked backends with a prompt mid-stream): page maintenance +
        chunk writes + the grouped decode step, still one dispatch."""
        self.n_traces["step_prefill"] += 1
        return self._megastep_body(params, gstate, prefill)

    def _chunk_rows0(self, mode: str) -> list[int]:
        """STATIC slot-leading cache rows of ``mode``'s group (row 0 of
        each slot — the row a chunked prefill writes)."""
        spec = self._groups[mode]
        lo = self._row_lo[mode]
        return [lo + i * spec.rows_per_slot for i in range(spec.n_slots)]

    def _write_chunks(self, params, gstate, prefill):
        """Apply the staged prefill chunk lanes (every group, idle lanes
        are ``n_valid == 0`` no-ops) inside the megastep."""
        if prefill is None:
            return gstate
        cache = gstate.cache
        for mode, (tokens, pos0, n_valid) in zip(self.mode_names, prefill):
            cache = self.backend.prefill_chunks_cache(
                params, cache, self._chunk_rows0(mode), tokens, pos0,
                n_valid)
        return GroupedState(groups=gstate.groups, cache=cache)

    def _megastep_body(self, params, gstate, prefill):
        """One fused device step, the steady-state iteration's ONLY
        dispatch: (paged) plan page maintenance on device, then — unless
        the pool is exhausted, in which case the whole step is an identity
        pass-through so the host can preempt and replay it exactly —
        apply the plan, write this iteration's prefill chunks, and run the
        grouped decode step. Returns ``(gstate, bundle)`` where the bundle
        holds everything the host syncs on: the finished mask, committed
        counts + greedy stream deltas, and the page counters that feed the
        mirrored admission accounting."""
        specs = tuple(self._groups.values())
        handle = self.backend.step_handle(params)
        n_out0 = self._slot_counts(gstate)
        plan = None
        if self.ecfg.paged:
            n_pages, ps = self._paged_geometry()
            blocks = tuple(self.allocator._blocks[m]
                           for m in self.mode_names)
            plan_prefill = None
            if prefill is not None:
                C = max(1, int(self.ecfg.prefill_chunk))
                plan_prefill = tuple(
                    (self._chunk_rows0(m), pos0, n_valid, C)
                    for m, (_, pos0, n_valid)
                    in zip(self.mode_names, prefill))
            shards = ((self.n_shards, self._row_shard)
                      if self.n_shards > 1 else None)
            plan = device_page_plan(specs, blocks, ps, n_pages, gstate,
                                    prefill=plan_prefill, shards=shards)

            def body(g):
                g = GroupedState(groups=g.groups,
                                 cache=apply_page_plan(g.cache, plan))
                g = self._write_chunks(params, g, prefill)
                return grouped_step(specs, handle, g)

            gstate = jax.lax.cond(plan.exhausted, lambda g: g, body, gstate)
        else:
            gstate = self._write_chunks(params, gstate, prefill)
            gstate = grouped_step(specs, handle, gstate)
        return gstate, self._make_bundle(gstate, n_out0, plan)

    def _slot_counts(self, gstate) -> jnp.ndarray:
        """(n_slots,) committed-token counts on each slot's row 0, global
        slot order (groups are slot-contiguous in declaration order)."""
        return jnp.concatenate([gs.n_out[:, 0]
                                for gs in gstate.groups])

    def _make_bundle(self, gstate, n_out0, plan) -> dict:
        """The megastep's host-sync bundle: small fixed-shape arrays (the
        per-iteration readback is O(n_slots), never the session state)."""
        specs = list(self._groups.values())
        maxW = max([s.draft_len + 1 for s in specs if s.kind == "greedy"],
                   default=1)
        finished = jnp.concatenate([gs.finished.all(axis=1)
                                    for gs in gstate.groups])
        n_out1 = self._slot_counts(gstate)
        n_new = n_out1 - n_out0
        w = jnp.arange(maxW, dtype=jnp.int32)
        deltas, lo = [], 0
        for spec, gs in zip(specs, gstate.groups):
            S = spec.n_slots
            if spec.kind == "greedy":
                n0 = n_out0[lo:lo + S]
                idx = jnp.clip(n0[:, None] + w[None, :], 0,
                               spec.max_new - 1)
                tok = jnp.take_along_axis(gs.tokens[:, 0], idx, axis=1)
                d = jnp.where(w[None, :] < n_new[lo:lo + S, None], tok, 0)
            else:
                # beams reorder mid-flight: only terminal reads are truthful
                d = jnp.zeros((S, maxW), jnp.int32)
            deltas.append(d)
            lo += S
        bundle = dict(finished=finished, n_out=n_out1, n_new=n_new,
                      delta=jnp.concatenate(deltas, axis=0))
        if plan is not None:
            n_pages, _ = self._paged_geometry()
            spent = jnp.sum(plan.need_by_group)
            bundle.update(
                exhausted=plan.exhausted,
                # free pages right after allocation (the peak-usage feed);
                # an exhausted plan allocates nothing
                n_free_alloc=jnp.where(plan.exhausted, plan.n_free,
                                       plan.n_free - spent),
                # recounted POST-step: winner sync / beam reorder orphan
                # pages inside the step, and the mirror must see them free
                n_free_final=device_free_pages(gstate.cache, n_pages),
                need=plan.need_by_group)
            if plan.need_by_shard is not None:
                # per-shard mirrors of the three counters above: the host
                # keeps shard-local admission accounting and attributes
                # exhaustion to the shard that is actually short
                bundle.update(
                    need_sh=plan.need_by_shard,
                    n_free_alloc_sh=jnp.where(
                        plan.exhausted, plan.n_free_by_shard,
                        plan.n_free_by_shard - plan.need_by_shard),
                    n_free_final_sh=device_free_pages_by_shard(
                        gstate.cache, n_pages, self.n_shards),
                    exhausted_sh=plan.exhausted_by_shard)
            if self._prefix_sharing:
                # post-step row0 block tables for every slot: the host
                # reads a finishing slot's committed prompt pages from here
                # to insert them into the radix tree — no extra sync
                rows0 = [self._slot_row0(s) for s in range(self.n_slots)]
                bundle["row0_pages"] = read_row_pages(gstate.cache, rows0,
                                                      self._prefix_pad)
        else:
            bundle.update(exhausted=jnp.asarray(False),
                          n_free_alloc=jnp.int32(0),
                          n_free_final=jnp.int32(0),
                          need=jnp.zeros((len(specs),), jnp.int32))
        return bundle

    def _slot_rows(self, mode: str, slot):
        spec = self._groups[mode]
        return (self._row_lo[mode] + slot * spec.rows_per_slot
                + jnp.arange(spec.rows_per_slot))

    def _swap_group(self, gstate, gi: int, gs):
        groups = gstate.groups[:gi] + (gs,) + gstate.groups[gi + 1:]
        return GroupedState(groups=groups, cache=gstate.cache)

    def _make_admit(self, mode: str):
        """Jitted admission into a slot of ``mode``'s group; ``slot`` is a
        traced LOCAL slot index — no recompilation per admission, and
        admitting into this group never retraces the other groups' math.

        Monolithic backends (seq2seq) do all cache work here — encode the
        query, scatter cross-attn K/V + memory mask, reset the slot's
        decode state. Chunked backends only recycle the slot's cache rows;
        the prompt then streams in via ``_make_chunk`` and the slot
        activates in ``_make_finish``.

        ``gen`` is the request's fixed-shape generation-param bundle
        (``ResolvedParams.device_args``): traced VALUES, so heterogeneous
        per-request params reuse this one trace."""
        spec = self._groups[mode]
        gi = self.mode_names.index(mode)
        be = self.backend

        if be.chunked:
            def admit(params, gstate, slot):
                self.n_traces["admit", mode] += 1
                rows = self._slot_rows(mode, slot)
                cache = be.begin_cache(gstate.cache, rows)
                return GroupedState(groups=gstate.groups, cache=cache)

            return jax.jit(admit, donate_argnums=(1,))

        def admit(params, gstate, slot, gen, *args):
            self.n_traces["admit", mode] += 1
            rows = self._slot_rows(mode, slot)
            cache = be.admit_cache(params, gstate.cache, rows, *args)
            last, pos0, drafts, dmask = be.reset_args(*args)
            max_out, stop_ids, eff_dl, eff_beams = gen
            gs = reset_slot(spec, gstate.groups[gi], slot, last, pos0,
                            drafts, dmask, max_out=max_out,
                            stop_ids=stop_ids, eff_dl=eff_dl,
                            eff_beams=eff_beams)
            return self._swap_group(
                GroupedState(groups=gstate.groups, cache=cache), gi, gs)

        return jax.jit(admit, donate_argnums=(1,))

    def _make_admit_cached(self, mode: str):
        """Jitted admission variant for the seq2seq ``prefix_cache`` path:
        the encoder output arrives precomputed (host LRU over repeated
        sources), so admission is just the scatter + slot reset. Hit and
        miss BOTH go through this trace — a miss first runs the jitted
        encode — keeping shared and cold admissions of one engine
        byte-identical by construction."""
        spec = self._groups[mode]
        gi = self.mode_names.index(mode)
        be = self.backend

        def admit(params, gstate, slot, gen, mkv, mask, drafts, dmask):
            self.n_traces["admit_cached", mode] += 1
            rows = self._slot_rows(mode, slot)
            cache = be.admit_cache_precomputed(params, gstate.cache, rows,
                                               mkv, mask)
            last, pos0, drafts, dmask = be.reset_args(None, drafts, dmask)
            max_out, stop_ids, eff_dl, eff_beams = gen
            gs = reset_slot(spec, gstate.groups[gi], slot, last, pos0,
                            drafts, dmask, max_out=max_out,
                            stop_ids=stop_ids, eff_dl=eff_dl,
                            eff_beams=eff_beams)
            return self._swap_group(
                GroupedState(groups=gstate.groups, cache=cache), gi, gs)

        return jax.jit(admit, donate_argnums=(1,))

    def _make_finish(self, mode: str):
        """Jitted: prefill done — siblings adopt row 0's context (dense
        broadcast / paged table alias) and the slot goes live."""
        spec = self._groups[mode]
        gi = self.mode_names.index(mode)
        be = self.backend

        def finish(params, gstate, slot, gen, *args):
            self.n_traces["finish", mode] += 1
            rows = self._slot_rows(mode, slot)
            cache = be.finish_cache(gstate.cache, rows)
            last, pos0, drafts, dmask = be.reset_args(*args)
            max_out, stop_ids, eff_dl, eff_beams = gen
            gs = reset_slot(spec, gstate.groups[gi], slot, last, pos0,
                            drafts, dmask, max_out=max_out,
                            stop_ids=stop_ids, eff_dl=eff_dl,
                            eff_beams=eff_beams)
            return self._swap_group(
                GroupedState(groups=gstate.groups, cache=cache), gi, gs)

        return jax.jit(finish, donate_argnums=(1,))

    def _make_release(self, mode: str):
        """Jitted evict + (paged) unmap of a LOCAL slot of ``mode``'s group
        so the allocator's next reclaim returns its pages."""
        spec = self._groups[mode]
        gi = self.mode_names.index(mode)
        lo = self._row_lo[mode]
        paged = self.ecfg.paged

        def release(gstate, slot):
            gs = release_slot(gstate.groups[gi], slot)
            groups = gstate.groups[:gi] + (gs,) + gstate.groups[gi + 1:]
            cache = gstate.cache
            if paged:
                rows = (lo + slot * spec.rows_per_slot
                        + jnp.arange(spec.rows_per_slot))
                cache = unmap_cache_rows(cache, rows)
            return GroupedState(groups=groups, cache=cache)

        # donate like step/admit: eviction must not copy the whole cache
        return jax.jit(release, donate_argnums=(0,))

    def _slot_of(self, slot: int) -> tuple[str, int]:
        """Global scheduler slot -> (mode, local slot in its group)."""
        return self._slot_map[slot]

    def _paged_geometry(self) -> tuple[int, int]:
        """(n_pages, page_size); default pool = worst case for all rows of
        all groups — the paged *layout* with no oversubscription. Set
        ``n_pages`` lower to oversubscribe HBM (admission then defers on
        pool pressure)."""
        ecfg = self.ecfg
        if self.cfg.sliding_window:
            raise NotImplementedError(
                "paged serving sessions require sliding_window == 0: "
                "PageAllocator maps a linear block space and does not model "
                "the window's block ring")
        if not self.backend.pageable():
            raise ValueError(
                f"{self.cfg.name}: backend has nothing to page — serve dense")
        ps = ecfg.page_size
        worst = sum(s.n_rows * (-(-self.backend.row_len(s) // ps))
                    for s in self._groups.values())
        # prefix sharing retains up to n_cells pages beyond the rows' worst
        # case, so the no-oversubscription default grows by that many
        if ecfg.n_pages is not None:
            n_pages = ecfg.n_pages
            if n_pages % self.n_shards:
                raise ValueError(
                    f"EngineConfig.n_pages={n_pages} must divide into "
                    f"{self.n_shards} equal per-shard pool segments")
        else:
            # sharded: round up to equal segments so every shard's pool
            # covers its slots' worst case (+ the shared trash page,
            # which sits inside shard 0's segment)
            n_pages = worst + self._n_cells + 1
            n_pages = self.n_shards * (-(-n_pages // self.n_shards))
        return n_pages, ps

    def _finished_mask(self, gstate) -> np.ndarray:
        """(n_slots,) bool by global slot id (groups are slot-contiguous in
        declaration order, matching ``_slot_base``). Mid-prefill slots are
        never finished — their SessionState is still the released one."""
        mask = np.concatenate([np.asarray(gs.finished).all(axis=1)
                               for gs in gstate.groups])
        for slot in self._prefilling:
            mask[slot] = False
        return mask

    def _slot_row0(self, slot: int) -> int:
        mode, local = self._slot_of(slot)
        spec = self._groups[mode]
        return self._row_lo[mode] + local * spec.rows_per_slot

    # -- dispatch-ahead drive hooks ------------------------------------------
    def _stage_chunks(self):
        """Build this iteration's prefill chunk lanes from the mid-prefill
        cursors: a per-group ``(tokens (S_g, C), pos0, n_valid)`` tuple
        covering EVERY group (idle lanes are ``n_valid == 0``), or None
        when nothing is mid-prefill — the decode-only megastep variant
        dispatches instead. One chunk per slot per iteration, so a long
        admission never stalls resident decoding. The cursor lives on the
        host record, NOT the Request: a preempted request requeues with
        its chunk plan intact and replays deterministically."""
        staged = [s for s in sorted(self._prefilling)
                  if self._prefilling[s]["next"]
                  < len(self._prefilling[s]["chunks"])]
        if not staged:
            return None, []
        C = max(1, int(self.ecfg.prefill_chunk))
        toks = {m: np.zeros((spec.n_slots, C), np.int32)
                for m, spec in self._groups.items()}
        pos0 = {m: np.zeros((spec.n_slots,), np.int32)
                for m, spec in self._groups.items()}
        nval = {m: np.zeros((spec.n_slots,), np.int32)
                for m, spec in self._groups.items()}
        for slot in staged:
            rec = self._prefilling[slot]
            mode = rec["mode"]
            local = slot - self._slot_base[mode]
            tokens, p0, nv = rec["chunks"][rec["next"]]
            toks[mode][local] = np.asarray(tokens)
            pos0[mode][local] = p0
            nval[mode][local] = nv
        prefill = tuple((jnp.asarray(toks[m]), jnp.asarray(pos0[m]),
                         jnp.asarray(nval[m])) for m in self.mode_names)
        return prefill, staged

    def _dispatch_step(self, state):
        """Scheduler ``dispatch`` hook: issue ONE fused megastep (async —
        JAX dispatch returns immediately) and snapshot who it was issued
        for (resident rids, mid-prefill slots, staged chunks). Exhaustion
        replays re-stage from the then-current cursors, so a preempted
        victim's lanes drop out of the retry automatically."""
        prefill, staged = (self._stage_chunks() if self.backend.chunked
                           else (None, []))
        self._staged_slots = staged
        self._dispatch_rids = {s: r.rid
                               for s, r in self.scheduler._resident.items()}
        self._dispatch_prefilling = set(self._prefilling)
        with jax.profiler.TraceAnnotation("serve/megastep"):
            if prefill is None:
                state, bundle = self._megastep_fn(self.params, state)
            else:
                state, bundle = self._megastep_prefill_fn(
                    self.params, state, prefill)
        self._n_dispatched += 1
        self.n_dispatches += 1
        self._bundle = bundle
        return state

    def _sync_step(self) -> dict:
        """Scheduler ``sync`` hook: block on the in-flight megastep's
        output bundle — the iteration's ONLY device readback — then apply
        its host-side consequences: advance chunk cursors, activate slots
        whose prompt is fully written, refresh the mirrored page counters,
        stash the stream deltas, and build the eviction mask (guarded by
        the dispatch-time rid snapshot, so a slot recycled since dispatch
        is never evicted by a stale mask)."""
        with jax.profiler.TraceAnnotation("serve/readout"):
            out = {k: np.asarray(v) for k, v in self._bundle.items()}
        t = time.perf_counter()
        if self._last_sync_t is not None:
            self._step_gaps.append(t - self._last_sync_t)
            if len(self._step_gaps) > 4096:
                del self._step_gaps[:2048]
        self._last_sync_t = t
        if bool(out["exhausted"]):
            # all-or-nothing: the dispatched step applied NOTHING. Hint
            # the scheduler at the first group whose cumulative need
            # overflows the pool (the host walk's in-group-victim analog)
            # and — sharded — at the first shard that is actually short,
            # so preemption/replay stays shard-local.
            n_free, run, prefer = int(out["n_free_alloc"]), 0, None
            for gi, m in enumerate(self.mode_names):
                run += int(out["need"][gi])
                if run > n_free:
                    prefer = m
                    break
            shard = None
            if "exhausted_sh" in out:
                ex = np.asarray(out["exhausted_sh"], bool)
                shard = int(np.argmax(ex)) if ex.any() else None
            return {"exhausted": True, "group": prefer, "shard": shard}
        self._dispatch_samples.append(self.n_dispatches - self._disp_mark)
        if len(self._dispatch_samples) > 4096:
            del self._dispatch_samples[:2048]
        self._disp_mark = self.n_dispatches
        for slot in self._staged_slots:     # dispatched chunks are written
            rec = self._prefilling.get(slot)
            if rec is not None:
                rec["next"] += 1
        self._staged_slots = []
        for slot in sorted(self._dispatch_prefilling):
            rec = self._prefilling.get(slot)
            if rec is None or rec["next"] < len(rec["chunks"]):
                continue
            # prompt fully written: siblings adopt row 0 and the slot goes
            # live for the NEXT dispatch
            mode, req = rec["mode"], rec["req"]
            local = slot - self._slot_base[mode]
            self.scheduler.state = self._finish_fns[mode](
                self.params, self.scheduler.state, jnp.int32(local),
                req.gen, *req.args)
            self.n_dispatches += 1
            if self.radix is not None and rec.get("body") is not None:
                # the prompt is committed: publish its full pages into the
                # radix tree so later siblings can alias them
                self._radix_insert(slot, rec, out)
            del self._prefilling[slot]
            self._decoding.add(slot)
            if self.allocator is not None:
                spec = self._groups[mode]
                row0 = self._slot_row0(slot)
                self.allocator.unpin_rows(
                    range(row0, row0 + spec.rows_per_slot))
        if self.allocator is not None:
            self.allocator.peak_pages = max(
                self.allocator.peak_pages,
                (self.allocator.n_pages - 1) - int(out["n_free_alloc"]))
            self.pages_allocated += int(out["need"].sum())
            self._mirror_free = int(out["n_free_final"])
            if "n_free_final_sh" in out:
                self._mirror_free_sh = [int(x)
                                        for x in out["n_free_final_sh"]]
                self.allocator.note_peak(out["n_free_alloc_sh"])
            # bookings made before this bundle's dispatch are now visible
            # in the device counter; keep only the ones it cannot see yet
            self._booked = [b for b in self._booked
                            if b[0] >= self._n_dispatched]
        self._stream_bundle = dict(
            n_out=out["n_out"], n_new=out["n_new"], delta=out["delta"],
            # mid-prefill slots' session rows still hold the previous
            # occupant's counts: not this rid's tokens, never streamed
            rids={s: r for s, r in self._dispatch_rids.items()
                  if s not in self._dispatch_prefilling})
        mask = np.asarray(out["finished"], bool).copy()
        for slot in range(self.n_slots):
            sreq = self.scheduler._resident.get(slot)
            rid = self._dispatch_rids.get(slot)
            if rid is None or sreq is None or sreq.rid != rid:
                mask[slot] = False
        for slot in self._dispatch_prefilling:
            mask[slot] = False
        return {"exhausted": False, "finished": mask}

    def _mirror_recount(self) -> None:
        """Refresh the mirrored free counter straight from the device's
        block tables (the one blocking read on this path). The scheduler's
        state already carries every dispatch issued so far, so bookings
        stamped before the latest dispatch are visible in the recount."""
        n_pages, _ = self._paged_geometry()
        self._mirror_free = int(device_free_pages(
            self.scheduler.state.cache, n_pages))
        if self.n_shards > 1:
            self._mirror_free_sh = [
                int(x) for x in device_free_pages_by_shard(
                    self.scheduler.state.cache, n_pages, self.n_shards)]
        self._booked = [b for b in self._booked
                        if b[0] >= self._n_dispatched]

    def _mirror_admit_ok(self, state, mode) -> bool:
        """Paged admission gate on the MIRRORED free counter (last synced
        bundle) net of bookings the device has not seen yet — no device
        readback in the steady state, unlike ``PageAllocator.can_admit``.
        The gate is a thrash limiter, not a safety invariant:
        over-admission surfaces as the megastep's exhaustion flag and
        preempt-and-replay. A refusal first recounts from the device:
        evictions between syncs free pages the mirror cannot see (no
        bundle arrives while nothing is resident), and refusing on the
        stale counter would wedge admission permanently."""
        need = self.allocator.admit_pages_for(mode)
        booked = sum(b[-1] for b in self._booked)
        if self._mirror_free - booked >= need:
            return True
        self._mirror_recount()
        booked = sum(b[-1] for b in self._booked)
        # still short: retained prefix pages are reclaimable capacity —
        # evict LRU radix nodes (monotone progress, the tree only shrinks)
        # before refusing the admission
        while (self._mirror_free - booked < need and self._radix_reclaim()):
            self._mirror_recount()
            booked = sum(b[-1] for b in self._booked)
        return self._mirror_free - booked >= need

    # -- sharded placement ---------------------------------------------------
    def _shard_headroom(self, shard: int) -> int:
        """How much room shard ``shard`` has for new work: mirrored free
        pages net of unseen bookings (paged), or minus its resident count
        (dense — fewer residents == more room)."""
        if self.allocator is not None:
            booked = sum(b[-1] for b in self._booked if b[1] == shard)
            return self._mirror_free_sh[shard] - booked
        return -sum(1 for s in self.scheduler._resident
                    if self._shard_of_slot.get(s) == shard)

    def _shard_admit_ok(self, mode: str, shard: int) -> bool:
        """Per-shard analog of ``_mirror_admit_ok``: can ``shard``'s pool
        segment cover one ``mode`` admission's worst-case first step?
        Refusals recount from the device, then reclaim cached prefix
        pages FROM THIS SHARD before giving up."""
        need = self.allocator.admit_pages_for(mode)
        if self._shard_headroom(shard) >= need:
            return True
        self._mirror_recount()
        while (self._shard_headroom(shard) < need
               and self._radix_reclaim(shard)):
            self._mirror_recount()
        return self._shard_headroom(shard) >= need

    def _shard_order(self, mode: str, payload, avail: set) -> list[int]:
        """Shard preference for one admission: the shard holding the
        request's cached prefix pages first (aliasing stays local — the
        child decodes next to its parent's pages), then the rest by
        descending headroom (least-loaded), ties to the lowest shard id."""
        pref: list[int] = []
        req = payload[1]
        if self.radix is not None and req.prompt is not None:
            # non-mutating probe: placement must not skew LRU/hit stats,
            # _admit_match_prefix does the real (counted) match later
            chain = self.radix.peek(self.backend.prompt_body(req))
            depth = (len(chain) // self._align_pages) * self._align_pages
            if depth > 0:
                sh = self.allocator.shard_of_page(chain[depth - 1].page)
                if sh in avail:
                    pref.append(sh)
        rest = sorted((s for s in avail if s not in pref),
                      key=lambda s: (-self._shard_headroom(s), s))
        return pref + rest

    def _place_slot(self, mode: str, free: list[int], payload):
        """Scheduler ``place`` hook (sharded engines): pick the slot —
        and thereby the data shard — for the group head's admission, or
        None to defer when no shard can cover it this iteration."""
        by_shard: dict[int, list[int]] = {}
        for s in free:
            by_shard.setdefault(self._shard_of_slot[s], []).append(s)
        for sh in self._shard_order(mode, payload, set(by_shard)):
            if self.allocator is None or self._shard_admit_ok(mode, sh):
                return min(by_shard[sh])
        return None

    def shard_stats(self) -> dict:
        """Per-shard balance counters for the sharded benchmark mode."""
        out = {"n_shards": self.n_shards,
               "admitted_by_shard": list(self._admits_by_shard)}
        admits = self._admits_by_shard
        mean = sum(admits) / max(1, len(admits))
        out["admit_imbalance"] = (max(admits) / mean) if mean else 1.0
        if isinstance(self.allocator, ShardedPageAllocator):
            alloc = self.allocator
            out["peak_pages_by_shard"] = list(alloc.peak_pages_by_shard)
            out["shard_capacity"] = [alloc.shard_capacity(s)
                                     for s in range(self.n_shards)]
        return out

    def _new_scheduler(self) -> ContinuousScheduler:
        ecfg = self.ecfg
        paged = self._paged_geometry() if ecfg.paged else None
        # index rows ride after the group rows: block-table-only rows whose
        # cells pin retained radix pages (decode lanes never touch them)
        cache = self.backend.init_cache(self.n_rows + self._n_index_rows,
                                        self.cache_len, paged=paged)
        self._prefilling, self._decoding = {}, set()
        # prefix-sharing state: radix tree, per-slot acquired chains, the
        # seq2seq encoder-output LRU, reuse counters, and the lineage map
        # backing the tree-of-requests API (rid -> query/parent/children/
        # priority/owned radix nodes; bounded like _done)
        self.radix = (RadixPageCache(ecfg.page_size, self._n_cells)
                      if self._prefix_sharing else None)
        self._slot_chains: dict[int, list] = {}
        self._encode_lru: collections.OrderedDict = collections.OrderedDict()
        self._lineage: collections.OrderedDict = collections.OrderedDict()
        self._prefix_counters = {"lookups": 0, "hit_tokens": 0,
                                 "lookup_tokens": 0}
        self.pages_allocated = 0
        self.requests_admitted = 0
        # per-session dispatch-ahead state: the in-flight bundle, the
        # dispatch-time snapshots, and the mirrored admission counters
        self._bundle = None
        self._stream_bundle = None
        self._staged_slots = []
        self._dispatch_rids = {}
        self._dispatch_prefilling = set()
        self._booked = []   # (dispatch-generation stamp, shard, pages)
        self._n_dispatched = 0
        self._last_sync_t = None
        self._mirror_free_sh: list[int] = []
        self._admits_by_shard = [0] * self.n_shards

        def admit(state, slot, payload):
            mode, req = payload
            local = slot - self._slot_base[mode]
            shard = self._shard_of_slot.get(slot)
            if self.allocator is not None:
                # book the admission's worst-case first-step pages against
                # the mirror (and its shard's) until a later bundle's free
                # count reflects it
                self._booked.append(
                    (self._n_dispatched, shard,
                     self.allocator.admit_pages_for(mode)))
            if shard is not None:
                self._admits_by_shard[shard] += 1
            self.requests_admitted += 1
            with jax.profiler.TraceAnnotation("serve/admit"):
                if not self.backend.chunked:
                    self._decoding.add(slot)
                    self.n_dispatches += 1
                    if self._encode_reuse and req.prompt is not None:
                        return self._admit_encode_cached(state, mode, local,
                                                         req)
                    return self._admit_fns[mode](self.params, state,
                                                 jnp.int32(local), req.gen,
                                                 *req.args)
                # chunked: recycle the rows now; the prompt streams into
                # the megastep's chunk lanes and the slot activates at the
                # sync that observes its final chunk written
                state = self._admit_fns[mode](self.params, state,
                                              jnp.int32(local))
            self.n_dispatches += 1
            rec = {"mode": mode, "req": req, "next": 0,
                   "chunks": req.chunks, "depth0": 0, "body": None}
            if self.radix is not None and req.prompt is not None:
                state = self._admit_match_prefix(state, slot, rec)
            self._prefilling[slot] = rec
            if self.allocator is not None:
                spec = self._groups[mode]
                row0 = self._slot_row0(slot)
                self.allocator.pin_rows(range(row0,
                                              row0 + spec.rows_per_slot))
            return state

        def release(state, slot):
            mode, local = self._slot_of(slot)
            self._decoding.discard(slot)
            if slot in self._prefilling:   # preempted mid-prefill
                del self._prefilling[slot]
            chain = self._slot_chains.pop(slot, None)
            if chain:
                # drop the slot's hold on its aliased prefix chain; the
                # nodes stay in the tree (LRU-evictable once inactive)
                self.radix.release(chain)
            if self.allocator is not None:
                spec = self._groups[mode]
                row0 = self._slot_row0(slot)
                self.allocator.unpin_rows(range(row0,
                                               row0 + spec.rows_per_slot))
            self.n_dispatches += 1
            return self._release_fns[mode](state, jnp.int32(local))

        def step(state):
            # only a hand-driven legacy loop calls this; the scheduler's
            # pipelined drive uses the dispatch/sync hooks below
            state = self._dispatch_step(state)
            out = self._sync_step()
            if out.get("exhausted"):
                raise PoolExhausted("page pool exhausted",
                                    group=out.get("group"),
                                    shard=out.get("shard"))
            return state

        groups = {mode: list(range(base, base + self._groups[mode].n_slots))
                  for mode, base in self._slot_base.items()}
        hooks: dict = {"release": release, "groups": groups,
                       "finished": self._finished_mask,
                       "dispatch": self._dispatch_step,
                       "sync": self._sync_step}
        if self.n_shards > 1:
            # sharded: the engine picks the SLOT (and thereby the shard)
            # for every admission — prefix affinity first, least-loaded
            # shard otherwise — and pool-pressure preemption stays inside
            # the exhausted shard
            hooks.update(place=self._place_slot,
                         shards=dict(self._shard_of_slot))
        if ecfg.paged:
            be = self.backend
            alloc_kw = dict(
                n_pages=paged[0], page_size=paged[1],
                row_lens={m: be.row_len(s)
                          for m, s in self._groups.items()},
                prefill_blocks={m: be.prefill_blocks(paged[1])
                                for m in self._groups})
            if self.n_shards > 1:
                self.allocator = ShardedPageAllocator(
                    self._groups, n_shards=self.n_shards, **alloc_kw)
                self._mirror_free_sh = [
                    self.allocator.shard_capacity(s)
                    for s in range(self.n_shards)]
            else:
                self.allocator = PageAllocator(self._groups, **alloc_kw)
            self._mirror_free = self.allocator.n_pages - 1
            hooks.update(admit_ok=self._mirror_admit_ok)
            if self._n_index_rows:
                # the index rows' references must survive every reclaim
                self.allocator.pin_rows(
                    range(self.n_rows, self.n_rows + self._n_index_rows))
            if self._prefix_sharing:
                hooks.update(reclaim=self._radix_reclaim)
        state = grouped_init_state(tuple(self._groups.values()), cache)
        if self.mesh is not None:
            # commit the session state to its NamedShardings so the
            # donated megastep compiles as one SPMD program spanning the
            # mesh — still ONE dispatch per steady-state iteration
            state = jax.device_put(
                state, serving_state_shardings(state, self.mesh))
        return ContinuousScheduler(self.spec, state, admit=admit, step=step,
                                   policy=ecfg.overload, **hooks)

    # -- cross-request prefix sharing ---------------------------------------
    def _admit_match_prefix(self, state, slot: int, rec: dict):
        """Match an admitted prompt against the radix tree; alias the
        matched pages into the slot's row0 block table (one dispatch) and
        rewrite the host chunk plan to the unmatched suffix. The match is
        truncated to the chunk-grid alignment so the suffix prefill
        replays the cold run's exact chunk partition (token identity)."""
        req = rec["req"]
        ps = self.ecfg.page_size
        body = self.backend.prompt_body(req)
        rec["body"] = body
        chain = self.radix.match(body)
        depth = (len(chain) // self._align_pages) * self._align_pages
        if depth < len(chain):
            # keep the hit-rate stats honest about what was actually
            # aliased: the alignment rounds the match down
            self.radix.hit_tokens -= (len(chain) - depth) * ps
            chain = chain[:depth]
        if not chain:
            return state
        pages = np.full((self._prefix_pad,), -1, np.int32)
        pages[:depth] = [nd.page for nd in chain]
        state = self._alias_fn(state, jnp.int32(self._slot_row0(slot)),
                               jnp.asarray(pages), jnp.int32(depth))
        self.n_dispatches += 1
        self.radix.acquire(chain)
        self._slot_chains[slot] = chain
        rec["depth0"] = depth
        rec["chunks"] = self.backend.suffix_chunks(body, depth * ps)
        return state

    def _admit_encode_cached(self, state, mode: str, local: int, req):
        """Seq2seq admission through the encoder-output LRU: repeated
        sources skip the encoder entirely. Hit and miss both admit via the
        precomputed-scatter trace, so reuse never changes tokens."""
        src_np = np.asarray(req.prompt, np.int32)
        key = src_np.tobytes()
        c = self._prefix_counters
        c["lookups"] += 1
        c["lookup_tokens"] += int(src_np.size)
        ent = self._encode_lru.pop(key, None)
        if ent is None:
            ent = self._encode_fn(self.params, req.args[0])
            self.n_dispatches += 1
        else:
            c["hit_tokens"] += int(src_np.size)
        self._encode_lru[key] = ent
        while len(self._encode_lru) > self.ecfg.prefix_cache_entries:
            self._encode_lru.popitem(last=False)
        mkv, mask = ent
        return self._admit_cached_fns[mode](
            self.params, state, jnp.int32(local), req.gen, mkv, mask,
            req.args[1], req.args[2])

    def _radix_insert(self, slot: int, rec: dict, out: dict) -> None:
        """A prompt just finished prefilling: insert its full pages (read
        from the bundle's post-step row0 tables) into the radix tree and
        write the new nodes' index cells so the pages outlive the slot."""
        body = rec["body"]
        ps = self.ecfg.page_size
        n_full = len(body) // ps
        if n_full <= 0:
            return
        pages = np.asarray(out["row0_pages"][slot][:n_full])
        if (pages <= 0).any():
            return   # defensive: an unmapped/trash block is never shared
        new = self.radix.insert(body[:n_full * ps], pages, rec["depth0"])
        if not new:
            return
        sreq = self.scheduler._resident.get(slot)
        if sreq is not None:
            info = self._lineage.get(sreq.rid)
            if info is not None:
                info["nodes"].extend(new)
        self._write_cells([nd.cell for nd in new], [nd.page for nd in new])

    def _write_cells(self, cells: list, pages: list) -> None:
        """Write (cell -> page) index references, batched into fixed
        prefix_pad-wide dispatches of the one retained trace."""
        rows, blocks = radix_cell_coords(self.n_rows, self._table_blocks,
                                         cells)
        PB = self._prefix_pad
        for i in range(0, len(cells), PB):
            n = min(PB, len(cells) - i)
            r = np.zeros((PB,), np.int32)
            b = np.zeros((PB,), np.int32)
            p = np.full((PB,), -1, np.int32)
            r[:n], b[:n] = rows[i:i + n], blocks[i:i + n]
            p[:n] = pages[i:i + n]
            self.scheduler.state = self._retain_fn(
                self.scheduler.state, jnp.asarray(r), jnp.asarray(b),
                jnp.asarray(p), jnp.int32(n))
            self.n_dispatches += 1

    def _clear_cells(self, pairs: list) -> None:
        """Clear evicted nodes' (cell, page) index references so the pages
        fall out of the device refcount and return to the pool."""
        if not pairs:
            return
        cells = [c for c, _ in pairs]
        rows, blocks = radix_cell_coords(self.n_rows, self._table_blocks,
                                         cells)
        PB = self._prefix_pad
        for i in range(0, len(cells), PB):
            n = min(PB, len(cells) - i)
            r = np.zeros((PB,), np.int32)
            b = np.zeros((PB,), np.int32)
            r[:n], b[:n] = rows[i:i + n], blocks[i:i + n]
            self.scheduler.state = self._evict_cells_fn(
                self.scheduler.state, jnp.asarray(r), jnp.asarray(b),
                jnp.int32(n))
            self.n_dispatches += 1

    def _radix_reclaim(self, shard: int | None = None) -> bool:
        """Pool-pressure hook (scheduler ``reclaim``): evict LRU inactive
        radix nodes and clear their index cells, returning their pages to
        the device pool. Tried before preempting a resident request —
        cached prefixes are strictly cheaper to lose than live work.
        ``shard`` targets the eviction at one page-pool segment (the
        per-shard admission gate's relief valve)."""
        if self.radix is None or len(self.radix) == 0:
            return False
        where = (None if shard is None else
                 (lambda nd: self.allocator.shard_of_page(nd.page) == shard))
        pairs = self.radix.evict_lru(self._prefix_pad, where=where)
        if not pairs:
            return False
        self._clear_cells(pairs)
        return True

    def prefix_stats(self) -> dict:
        """Prefix-reuse counters for the planning benchmark: hit rate over
        prompt tokens, pages allocated per admitted request, tree size."""
        if self.radix is not None:
            rx = self.radix
            lookups, hit_t, look_t = rx.lookups, rx.hit_tokens, \
                rx.lookup_tokens
            nodes, inserted, evicted = len(rx), rx.inserted, rx.evicted
        else:
            c = self._prefix_counters
            lookups, hit_t, look_t = (c["lookups"], c["hit_tokens"],
                                      c["lookup_tokens"])
            nodes = len(self._encode_lru)
            inserted = evicted = 0
        return {
            "lookups": int(lookups),
            "hit_tokens": int(hit_t),
            "lookup_tokens": int(look_t),
            "prefix_hit_rate": (hit_t / look_t) if look_t else 0.0,
            "nodes": int(nodes),
            "inserted": int(inserted),
            "evicted": int(evicted),
            "pages_allocated": int(self.pages_allocated),
            "requests_admitted": int(self.requests_admitted),
            "pages_per_request": (self.pages_allocated
                                  / self.requests_admitted
                                  if self.requests_admitted else 0.0),
        }

    def clear_prefix_cache(self) -> int:
        """Drop every inactive radix node (clearing its index cells) /
        the whole encoder-output LRU. Returns the number of radix nodes
        dropped (pages made reclaimable)."""
        self._encode_lru.clear()
        if self.radix is None:
            return 0
        pairs = self.radix.evict_lru(len(self.radix))
        self._clear_cells(pairs)
        return len(pairs)

    # -- tree-of-requests (search-tree serving) ------------------------------
    def submit_child(self, parent, suffix, *, arrival: float = 0.0,
                     mode: str | None = None,
                     params: GenerationParams | None = None,
                     priority: int | None = None,
                     deadline: float | None = None) -> RequestHandle:
        """Submit a child whose prompt extends ``parent``'s (prompt +
        ``suffix``) — the planning-search expansion step. Mode and
        priority default to the parent's (search cost accrues down the
        tree, so children inherit their subtree's urgency); the shared
        prefix is served from the radix cache when prefix sharing is on."""
        prid = int(parent)
        info = self._lineage.get(prid)
        if info is None:
            raise KeyError(
                f"parent request {prid} is unknown to this session "
                f"(reset(), or the bounded lineage store evicted it)")
        pq = info["query"]
        if isinstance(pq, str):
            if not isinstance(suffix, str):
                raise TypeError("parent query is a string; the child "
                                "suffix must be a string too")
            q = pq + suffix
        else:
            q = np.concatenate([np.asarray(pq, np.int32).reshape(-1),
                                np.asarray(suffix, np.int32).reshape(-1)])
        h = self.submit(q, arrival=arrival, mode=mode or info["mode"],
                        params=params,
                        priority=(info["priority"] if priority is None
                                  else priority),
                        deadline=deadline)
        self._lineage[int(h)]["parent"] = prid
        info["children"].append(int(h))
        return h

    def cancel_subtree(self, rid: int) -> int:
        """Cancel ``rid`` and every known descendant (a pruned search
        subtree), then drop the pruned requests' radix nodes — the whole
        cached page subtree returns to the pool unless a node is still
        active under a live request outside the subtree, or shared via an
        ancestor that survives. Returns the number newly cancelled."""
        order: list[int] = []
        stack, seen = [int(rid)], set()
        while stack:
            r = stack.pop()
            if r in seen:
                continue
            seen.add(r)
            order.append(r)
            info = self._lineage.get(r)
            if info is not None:
                stack.extend(info["children"])
        n = sum(1 for r in order if self._cancel(r))
        if self.radix is not None:
            pairs: list = []
            for r in order:
                info = self._lineage.get(r)
                if info is None:
                    continue
                for node in info["nodes"]:
                    # guard against nodes already dropped (LRU eviction,
                    # or a shallower ancestor handled earlier in `order`)
                    if self.radix._nodes_by_cell.get(node.cell) is node:
                        pairs.extend(self.radix.drop_subtree(node))
                info["nodes"] = []
            self._clear_cells(pairs)
        return n

    def loop_stats(self) -> dict:
        """Host-loop instrumentation for the serving benchmark: total
        jitted dispatches, dispatches per scheduler iteration (steady
        state == 1.0: the fused megastep), and the host step-gap (seconds
        between consecutive bundle syncs) p50/p95."""
        gaps = sorted(self._step_gaps)

        def pct(q):
            if not gaps:
                return 0.0
            return gaps[min(len(gaps) - 1, int(q * len(gaps)))]

        samples = self._dispatch_samples
        return {
            "n_dispatches": self.n_dispatches,
            "n_iterations": len(samples),
            "dispatches_per_iteration": (sum(samples) / len(samples)
                                         if samples else 0.0),
            "steady_iterations_one_dispatch": sum(1 for s in samples
                                                  if s == 1),
            "step_gap_p50_s": pct(0.50),
            "step_gap_p95_s": pct(0.95),
        }

    def cache_footprint(self) -> dict:
        """Self-attention cache HBM accounting for the serving benchmark.

        ``capacity_bytes``: what the session reserves up front.
        ``peak_bytes``: high-water mark actually touched (dense rows reserve
        their worst case, so peak == capacity there; paged sessions report
        the allocator's page high-water mark).
        ``contiguous_equiv_slots``: how many *primary-group* slots a
        contiguous-row cache could fit in the same capacity — the paged
        session serves ``n_slots`` > this when oversubscribed (the
        acceptance criterion).
        """
        spec = self.spec
        per_token = self.backend.per_token_bytes()
        row_bytes = self.backend.row_len(spec) * per_token
        if self.ecfg.paged:
            n_pages, ps = self._paged_geometry()
            page_bytes = ps * per_token
            alloc = self.allocator
            return {
                "kind": "paged", "page_size": ps, "n_pages": n_pages,
                "capacity_bytes": (n_pages - 1) * page_bytes,
                "peak_bytes": (alloc.peak_pages if alloc else 0) * page_bytes,
                "contiguous_equiv_slots":
                    ((n_pages - 1) * page_bytes)
                    // (spec.rows_per_slot * row_bytes),
            }
        cap = self.n_rows * self.cache_len * per_token
        return {"kind": "dense", "capacity_bytes": cap, "peak_bytes": cap,
                "contiguous_equiv_slots": self.n_slots}

    # -- request plumbing ----------------------------------------------------
    def _payload(self, query, mode: str,
                 params: GenerationParams | None = None):
        spec = self._groups[mode]
        rp = (params or GenerationParams()).resolve(spec)
        return (mode, self.backend.make_request(query, spec, rp))

    def _read_slot(self, state, slot: int) -> dict:
        mode, local = self._slot_of(slot)
        spec = self._groups[mode]
        gs = state.groups[self.mode_names.index(mode)]
        order = (np.argsort(-np.asarray(gs.logp[local]), kind="stable")
                 if spec.kind == "beam"
                 else np.arange(spec.n_beams))
        # per-request params trim the read-out to the request's own shape
        # (spec-ceiling requests read the full buffers — the legacy view)
        eff_k, eff_new = spec.n_beams, spec.max_new
        sreq = self.scheduler._resident.get(slot)
        if sreq is not None:
            rp = sreq.payload[1].params
            if rp is not None:
                eff_k, eff_new = rp.n_beams, rp.max_new
        return dict(
            tokens=np.asarray(gs.tokens[local])[order][:eff_k, :eff_new],
            lengths=np.asarray(gs.n_out[local])[order][:eff_k],
            logprobs=np.asarray(gs.logp[local])[order][:eff_k],
            n_calls=int(gs.n_calls[local]),
            accepted=int(gs.accepted[local]),
        )

    def _prediction(self, r: SlotResult, wall_s: float) -> Prediction:
        if self.tok is None:
            raise ValueError("predict()/predict_topn() need a tokenizer; "
                             "use submit() + serve() for raw-token sessions")
        smiles = [self.tok.decode(r.tokens[k])
                  for k in range(r.tokens.shape[0])]
        kind = self._groups[r.mode].kind if r.mode in self._groups else "greedy"
        logprobs = ([float(x) for x in r.logprobs]
                    if kind == "beam" else [0.0] * len(smiles))
        return Prediction(smiles=smiles, logprobs=logprobs,
                          n_calls=r.n_calls,
                          acceptance_rate=r.accepted / max(int(r.lengths[0]), 1),
                          wall_s=wall_s)

    # -- public API ----------------------------------------------------------
    def reset(self) -> None:
        """Drop all queued/resident requests and start a fresh session.
        The jitted step/admit functions (and their compilations) survive."""
        self.scheduler = self._new_scheduler()
        self._done, self._epoch, self._streams = {}, {}, {}
        self._pump = None
        self._pump_realtime = False
        self._dispatch_samples, self._step_gaps = [], []
        self._disp_mark = self.n_dispatches

    def submit_spec(self, rspec: RequestSpec) -> RequestHandle:
        """THE canonical entry point: enqueue one fully-specified
        ``RequestSpec`` and return its ``RequestHandle`` (an ``int`` — the
        request id — exposing ``.result()``/``.stream()``/``.cancel()``/
        ``.status``). Every other submission surface (``submit``,
        ``submit_child``, ``predict*``, the network front door) builds a
        spec and lands here.

        Overload behavior: a submission against a draining engine, or one
        whose group queue is at ``OverloadPolicy.shed_depth``, is refused
        with a terminal SHED record — the returned handle's ``.status`` is
        already ``RequestStatus.SHED`` and ``.result()`` raises
        ``RequestRejected`` carrying the scheduler's ``retry_after``
        estimate."""
        mode = self.default_mode if rspec.mode is None else rspec.mode
        if mode not in self._groups:
            raise KeyError(f"engine serves {self.mode_names}, got {mode!r}")
        payload = self._payload(rspec.query, mode, rspec.params)
        rid = self.scheduler.submit(payload, arrival=rspec.arrival,
                                    mode=mode, priority=rspec.priority,
                                    deadline=rspec.deadline)
        # a shed submission (queue at depth, or the scheduler draining)
        # produced a terminal record instead of a queue entry: land it in
        # the done-store NOW so handle.status is SHED synchronously
        for r in self.scheduler.drain_shed():
            self._finish_result(r)
        # lineage record for the tree-of-requests API (submit_child /
        # cancel_subtree): bounded like _done — an aged-out parent can no
        # longer be extended, which the search loop sees as a KeyError
        q = rspec.query if isinstance(rspec.query, str) else \
            np.asarray(rspec.query, np.int32).reshape(-1).copy()
        self._lineage[rid] = {"query": q, "parent": None, "children": [],
                              "priority": rspec.priority, "mode": mode,
                              "nodes": []}
        while len(self._lineage) > self._DONE_CAP:
            self._lineage.popitem(last=False)
        return RequestHandle(rid, self, mode=mode,
                             params=payload[1].params)

    def submit(self, query, *, arrival: float = 0.0,
               mode: str | None = None,
               params: GenerationParams | None = None,
               priority: int = 0,
               deadline: float | None = None) -> RequestHandle:
        """Thin sugar over ``submit_spec`` — builds the canonical
        ``RequestSpec`` from kwargs. ``query`` is a string (tokenized by
        the engine's tokenizer) or a 1-D array of token ids (decoder-only
        sessions without a chemistry tokenizer). ``arrival`` delays
        admission (steps in closed-loop serve(), seconds in realtime
        serve()); ``mode`` routes the request to that slot group (default:
        the engine's primary mode); ``params`` sets per-request generation
        knobs under the group's ceilings; higher ``priority`` admits first
        among arrived requests; past its ``deadline`` (serving clock) the
        request expires instead of running."""
        return self.submit_spec(RequestSpec(
            query=query, params=params or GenerationParams(), mode=mode,
            priority=priority, deadline=deadline, arrival=arrival))

    # -- step pump: one drive shared by serve()/result()/stream() -----------
    def serve_steps(self, *, realtime: bool = False):
        """Step-driven serving: a generator yielding the list of terminal
        ``SlotResult``s after every scheduler iteration (often empty)
        until the queue drains. Streaming token deltas are collected
        between iterations.

        Returns THE session's shared pump — the same drive that
        ``serve()`` and ``RequestHandle.result()``/``.stream()`` advance —
        so external stepping composes with the blocking calls instead of
        racing a second drive (and a second clock) against them. Once a
        drive drains, get a fresh generator for later submissions rather
        than resuming a kept reference."""
        return self._ensure_pump(realtime=realtime)

    def _serve_steps_impl(self, realtime: bool):
        for events in self.scheduler.steps(self._read_slot,
                                           realtime=realtime):
            self._collect_streams()
            for r in events:
                self._finish_result(r)
            yield events

    def _ensure_pump(self, realtime: bool = False):
        if self._pump is None:
            self._pump = self._serve_steps_impl(realtime)
            self._pump_realtime = realtime
        return self._pump

    def _pump_once(self) -> bool:
        """Advance the shared pump one scheduler iteration; False once the
        queue is drained. A pump whose drive has drained (nothing queued or
        resident) is disposed EAGERLY — not just on StopIteration — so
        work submitted after a completed drive starts a fresh one that can
        pick its own clock mode (serve(realtime=...))."""
        pump = self._ensure_pump()
        try:
            next(pump)
        except StopIteration:
            self._pump = None
            return False
        if not self.scheduler.pending:
            self._pump = None
        return True

    def _finish_result(self, r: SlotResult) -> None:
        self._done[r.rid] = r
        self._epoch[r.rid] = r
        # both stores are bounded (oldest insertion evicts): a session
        # driven purely through handles never calls serve(), so the epoch
        # dict must not grow with total requests served either
        while len(self._done) > self._DONE_CAP:
            self._done.pop(next(iter(self._done)))
        while len(self._epoch) > self._DONE_CAP:
            self._epoch.pop(next(iter(self._epoch)))
        st = self._streams.get(r.rid)
        if st is not None and not st["done"]:
            self._flush_stream_tail(st, r)

    def _flush_stream_tail(self, st: dict, r: SlotResult) -> None:
        """Final stream chunk: greedy-family tails from the cursor; beam
        modes deliver the winning beam whole (beams reorder mid-flight,
        so only the terminal ranking is truthful)."""
        if r.status == RequestStatus.FINISHED and r.tokens.shape[0]:
            kind = self._groups[r.mode].kind if r.mode in self._groups \
                else "greedy"
            lo = st["n"] if kind == "greedy" else 0
            tail = np.asarray(r.tokens[0][lo:int(r.lengths[0])])
            if tail.size:
                st["buf"].append(tail)
        st["done"] = True

    def _collect_streams(self) -> None:
        """Deliver committed-token deltas to live ``stream()`` consumers
        from the LAST SYNCED BUNDLE — greedy-family slots stream mid-flight
        with zero extra device readback; beam slots deliver at completion
        via the tail flush. A consumer that subscribed mid-flight missed
        earlier bundles and catches up once from the session state (the
        one-off blocking price of a late attach)."""
        live = {rid: st for rid, st in self._streams.items()
                if not st["done"]}
        sb = self._stream_bundle
        if not live or sb is None:
            return
        for slot, rid in sb["rids"].items():
            st = live.get(rid)
            if st is None:
                continue
            mode, local = self._slot_of(slot)
            if self._groups[mode].kind != "greedy":
                continue
            n_after = int(sb["n_out"][slot])
            n_new = int(sb["n_new"][slot])
            if n_after <= st["n"]:
                continue
            lo = st["n"] - (n_after - n_new)
            if lo >= 0:
                st["buf"].append(np.asarray(sb["delta"][slot, lo:n_new]))
                st["n"] = n_after
            elif not st.get("caught_up"):
                # one-off catch-up for a late attach: this read blocks on
                # the in-flight step, so pay it ONCE and ride the bundles
                # afterwards — any residual gap (tokens committed between
                # this read and the next bundle) is healed by the terminal
                # tail flush, which replays from the cursor
                gs = self.scheduler.state.groups[
                    self.mode_names.index(mode)]
                n = int(gs.n_out[local, 0])
                if n > st["n"]:
                    st["buf"].append(
                        np.asarray(gs.tokens[local, 0, st["n"]:n]))
                    st["n"] = n
                st["caught_up"] = True

    # -- request-level control (the RequestHandle surface) -------------------
    def request_status(self, rid: int) -> RequestStatus:
        r = self._done.get(rid)
        if r is not None:
            return r.status
        if any(sr.rid == rid for sr in self.scheduler._resident.values()):
            return RequestStatus.RUNNING
        if rid in self.scheduler._queued_by_rid:
            return RequestStatus.QUEUED
        # not in this session: reset() dropped it, it belongs to another
        # engine, or its terminal record aged out of the bounded store —
        # never QUEUED, so a done() poller cannot spin forever
        return RequestStatus.UNKNOWN

    def wait(self, rid: int) -> SlotResult:
        """Drive the pump until ``rid`` reaches a terminal record."""
        while rid not in self._done:
            if not self._pump_once() and rid not in self._done:
                raise KeyError(f"request {rid} is not part of this session "
                               f"(reset() drops pending requests)")
        return self._done[rid]

    def subscribe(self, rid: int) -> dict:
        """Attach a NON-BLOCKING stream sink to ``rid`` and return it —
        the front door's (``repro.serving.server``) subscription surface.
        The sink is the same dict ``_stream`` consumes: ``buf`` fills with
        committed-token delta arrays as bundles sync, ``done`` flips when
        the terminal tail is flushed. The caller drains ``buf`` between
        pump iterations; ``unsubscribe`` detaches."""
        st = self._streams.get(rid)
        if st is None:
            st = self._streams[rid] = {"buf": [], "n": 0, "done": False}
            r = self._done.get(rid)
            if r is not None:      # finished before anyone listened
                self._flush_stream_tail(st, r)
        return st

    def unsubscribe(self, rid: int) -> None:
        self._streams.pop(rid, None)

    def _stream(self, rid: int):
        """Generator behind ``RequestHandle.stream()``."""
        st = self.subscribe(rid)
        try:
            while True:
                while st["buf"]:
                    yield st["buf"].pop(0)
                if st["done"]:
                    break
                if rid in self._done:   # terminal but tail not flushed
                    self._flush_stream_tail(st, self._done[rid])
                    continue
                if not self._pump_once() and rid not in self._done:
                    raise KeyError(f"request {rid} is not part of this "
                                   f"session")
        finally:
            self._streams.pop(rid, None)
        r = self._done[rid]
        if r.status != RequestStatus.FINISHED:
            if r.status in (RequestStatus.SHED, RequestStatus.EXPIRED):
                raise RequestRejected(rid, r.status,
                                      retry_after=r.retry_after)
            raise RequestCancelled(rid, r.status)

    def stream(self, rid: int):
        """Deprecated engine-level entry — use ``RequestHandle.stream()``
        (one release of shim; the handle IS the rid, so
        ``handle.stream()`` is a drop-in)."""
        warnings.warn(
            "StreamingEngine.stream(rid) is deprecated; call "
            ".stream() on the RequestHandle returned by submit()",
            DeprecationWarning, stacklevel=2)
        return self._stream(rid)

    def _cancel(self, rid: int) -> bool:
        """Cancel a queued (dequeue) or resident (evict + reclaim pages)
        request. Returns False once the request is already terminal."""
        r = self.scheduler.cancel(rid)
        if r is None:
            return False
        self._finish_result(r)
        return True

    def cancel(self, rid: int) -> bool:
        """Deprecated engine-level entry — use ``RequestHandle.cancel()``
        (one release of shim)."""
        warnings.warn(
            "StreamingEngine.cancel(rid) is deprecated; call "
            ".cancel() on the RequestHandle returned by submit()",
            DeprecationWarning, stacklevel=2)
        return self._cancel(rid)

    # -- graceful drain (shutdown path) --------------------------------------
    @property
    def draining(self) -> bool:
        return self.scheduler.draining

    def begin_drain(self) -> int:
        """Enter drain mode WITHOUT blocking: every queued (non-resident)
        request is refused with a terminal SHED record + retry hint,
        residents keep decoding to completion (token-identical — nothing
        about their slots changes), and every later submission sheds
        immediately. Returns the number of requests shed. The front door
        calls this on shutdown and keeps pumping until residents finish;
        ``drain()`` is the blocking wrapper. ``reset()`` clears the mode."""
        self.scheduler.draining = True
        shed = self.scheduler.shed_queued()
        for r in shed:
            self._finish_result(r)
        return len(shed)

    def drain(self) -> dict[int, SlotResult]:
        """Blocking graceful shutdown: ``begin_drain()`` + pump until the
        residents finish. Returns the epoch's terminal records (finished
        residents AND the shed queue)."""
        self.begin_drain()
        while self._pump_once():
            pass
        out, self._epoch = self._epoch, {}
        return out

    def serve(self, *, realtime: bool = False) -> dict[int, SlotResult]:
        """Drain the queue with continuous batching; {rid: SlotResult} of
        every request that reached a terminal state since the last
        serve() (finished, cancelled, or expired). A drive's clock mode is
        fixed at its first pump — ``handle.result()``/``.stream()`` start
        closed-loop drives — so a mismatched ``realtime`` here is an error
        rather than a silent unit change."""
        if self._pump is not None and realtime != self._pump_realtime:
            raise RuntimeError(
                f"a {'realtime' if self._pump_realtime else 'closed-loop'} "
                f"drive is already in flight (handle.result()/stream() "
                f"pumps start closed-loop); serve(realtime={realtime}) "
                f"cannot switch clocks mid-drive — drain it first")
        self._ensure_pump(realtime=realtime)
        while self._pump_once():
            pass
        out, self._epoch = self._epoch, {}
        return out

    def _require_idle(self, caller: str) -> None:
        # the one-shot APIs drain the queue; running them with foreign
        # submit()ed requests pending would silently discard those results
        if self.scheduler.pending:
            raise RuntimeError(
                f"{caller} would drain {self.scheduler.pending} pending "
                f"submit()ed request(s); call serve() first")

    def predict(self, queries: Sequence[str]) -> list[Prediction]:
        """Compatibility wrapper (drop-in for ReactionEngine.predict,
        greedy/speculative): a thin batch loop over the request front door
        — ``submit()`` handles + a draining ``serve()``. New code should
        submit ``RequestSpec``s directly for per-request params, priority,
        streaming, and cancellation."""
        if self.ecfg.mode not in ("greedy", "speculative"):
            raise ValueError(f"predict() supports greedy/speculative, "
                             f"got {self.ecfg.mode}")
        self._require_idle("predict()")
        t0 = time.time()
        handles = [self.submit(q) for q in queries]
        # read the drained epoch dict, not handle.result(): a batch larger
        # than the bounded terminal store must not lose early results
        done = self.serve()
        wall = (time.time() - t0) / max(len(queries), 1)
        return [self._prediction(done[int(h)], wall) for h in handles]

    def predict_topn(self, query: str) -> Prediction:
        """Compatibility wrapper (drop-in for ReactionEngine.predict_topn,
        beam modes) — one query, n_beams candidates sorted by
        log-probability, via one front-door handle."""
        if self.spec.kind != "beam":
            raise ValueError(f"predict_topn() needs a beam mode, "
                             f"got {self.ecfg.mode}")
        self._require_idle("predict_topn()")
        t0 = time.time()
        handle = self.submit(query)
        done = self.serve()
        return self._prediction(done[int(handle)], time.time() - t0)
