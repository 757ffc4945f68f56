"""DecodeSession — the resumable fixed-slot decoding core.

Every decoding mode in this repo (greedy, speculative greedy, beam,
speculative beam) is one *pure step function* over the same fixed-slot
state instead of a bespoke closed-over ``lax.while_loop``:

  prefill   reset_slot() writes a request into a free slot (algorithm
            state here; the caller populates the model-cache rows)
  step      session_step() runs ONE verify/commit iteration for every
            slot simultaneously — shapes are fixed by the SessionSpec,
            so a single jitted step is reused across requests forever
  commit    the step itself commits accepted tokens and rolls the cache

This is what makes continuous batching possible: a scheduler
(``repro.serving.scheduler``) calls the step from the host, evicts slots
whose sequences finished, and admits queued requests into the freed rows
*without recompilation*. The one-shot decode functions
(``greedy_decode`` & co.) are thin ``lax.while_loop`` wrappers over the
same step, so batch-mode and streaming-mode outputs are token-identical
by construction.

Slot layout: ``n_slots`` (S) independent requests, each owning
``n_beams`` (K) beam rows × ``n_drafts`` (N_d) draft rows of the model
cache — cache row ``(s*K + k)*N_d + d``. Greedy-family modes are K=1;
non-speculative modes are N_d=1, DL=0. Inactive slots keep stepping on
garbage rows (fixed shapes); all math is row-independent, so resident
requests are unaffected — the invariant ``tests/test_session.py`` checks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.handles import DecoderHandle
from repro.core.tree_batch import (gather_rows, merge_rows, slice_rows,
                                   sync_winner)
from repro.models.attention import TRASH_PAGE, PagedKVCache

_NEG = -1e30


class SessionSpec(NamedTuple):
    """Static shape/mode bundle; hashable, so one jit per spec.

    The spec fixes the COMPILE-SHAPE CEILINGS of its slots: every request
    admitted into the session may use up to ``max_new`` tokens, ``n_beams``
    beams, ``n_drafts`` drafts of ``draft_len`` tokens, and ``n_stop``
    extra stop ids. Per-request values below these ceilings ride in
    ``SessionState`` device arrays (``max_out``/``eff_dl``/``eff_beams``/
    ``stop_ids``) so ragged generation params change ZERO traced shapes."""

    n_slots: int                 # S — concurrent requests
    n_beams: int                 # K — rows per request (1 = greedy family)
    n_drafts: int                # N_d — drafts verified per row per step
    draft_len: int               # DL — tokens per draft
    max_new: int
    eos_id: int
    pad_id: int = 0
    kind: str = "greedy"         # "greedy" (argmax accept) | "beam" (top-k)
    n_stop: int = 0              # per-slot extra stop ids (0 = eos only)

    @property
    def rows_per_slot(self) -> int:
        return self.n_beams * self.n_drafts

    @property
    def n_rows(self) -> int:
        return self.n_slots * self.rows_per_slot

    @property
    def cache_len(self) -> int:
        """Minimum cache length: every step writes at pos .. pos+DL."""
        return self.max_new + self.draft_len + 2


class SessionState(NamedTuple):
    """Per-slot decode state. Leading dims: (S, K) unless noted."""

    tokens: jnp.ndarray      # (S, K, max_new) committed output, pad after EOS
    logp: jnp.ndarray        # (S, K) cumulative log-prob (beam family)
    last: jnp.ndarray        # (S, K) last committed, not-yet-fed token
    pos: jnp.ndarray         # (S, K) absolute position of `last`
    n_out: jnp.ndarray       # (S, K) committed token count
    finished: jnp.ndarray    # (S, K) bool
    active: jnp.ndarray      # (S,) bool — slot holds a live request
    drafts: jnp.ndarray      # (S, N_d, DL) per-request source-copy drafts
    draft_mask: jnp.ndarray  # (S, N_d) bool
    n_calls: jnp.ndarray     # (S,) decoder forward passes while resident
    accepted: jnp.ndarray    # (S,) committed draft tokens (beam-0 path)
    # per-request generation params (<= the spec's ceilings; ragged values
    # never change a traced shape). Equal-to-ceiling values make every
    # consumer below an algebraic no-op, so default sessions stay
    # byte-identical to the pre-params step.
    max_out: jnp.ndarray     # (S,) per-slot token budget (<= spec.max_new)
    stop_ids: jnp.ndarray    # (S, n_stop) extra stop ids, -1 = unused
    eff_dl: jnp.ndarray      # (S,) effective draft length (<= DL)
    eff_beams: jnp.ndarray   # (S,) effective beam width (<= K)
    cache: Any               # model cache, batch rows = S*K*N_d


def init_state(spec: SessionSpec, cache: Any) -> SessionState:
    """All slots free. ``cache`` must have ``spec.n_rows`` batch rows and
    length >= ``spec.cache_len``."""
    S, K = spec.n_slots, spec.n_beams
    return SessionState(
        tokens=jnp.full((S, K, spec.max_new), spec.pad_id, jnp.int32),
        logp=jnp.full((S, K), _NEG, jnp.float32),
        last=jnp.zeros((S, K), jnp.int32),
        pos=jnp.zeros((S, K), jnp.int32),
        n_out=jnp.zeros((S, K), jnp.int32),
        finished=jnp.ones((S, K), bool),
        active=jnp.zeros((S,), bool),
        drafts=jnp.zeros((S, spec.n_drafts, spec.draft_len), jnp.int32),
        draft_mask=jnp.zeros((S, spec.n_drafts), bool),
        n_calls=jnp.zeros((S,), jnp.int32),
        accepted=jnp.zeros((S,), jnp.int32),
        max_out=jnp.full((S,), spec.max_new, jnp.int32),
        stop_ids=jnp.full((S, spec.n_stop), -1, jnp.int32),
        eff_dl=jnp.full((S,), spec.draft_len, jnp.int32),
        eff_beams=jnp.full((S,), spec.n_beams, jnp.int32),
        cache=cache,
    )


def reset_slot(spec: SessionSpec, state: SessionState, slot,
               last_token, start_pos, drafts, draft_mask, *,
               max_out=None, stop_ids=None, eff_dl=None,
               eff_beams=None) -> SessionState:
    """Prefill a slot's algorithm state (the caller populates the model
    cache rows). ``slot`` may be a traced scalar — no recompilation per
    admission. ``last_token``/``start_pos`` are scalars; ``drafts`` is
    (N_d, DL), ``draft_mask`` (N_d,). The generation params are optional
    traced scalars / a (n_stop,) array; omitted values default to the
    spec's ceilings (the pre-params behavior)."""
    K = spec.n_beams
    beam0 = jnp.where(jnp.arange(K) == 0, 0.0, _NEG).astype(jnp.float32)
    if max_out is None:
        max_out = spec.max_new
    if stop_ids is None:
        stop_ids = jnp.full((spec.n_stop,), -1, jnp.int32)
    if eff_dl is None:
        eff_dl = spec.draft_len
    if eff_beams is None:
        eff_beams = spec.n_beams
    return state._replace(
        tokens=state.tokens.at[slot].set(spec.pad_id),
        logp=state.logp.at[slot].set(beam0),
        last=state.last.at[slot].set(jnp.int32(last_token)),
        pos=state.pos.at[slot].set(jnp.int32(start_pos)),
        n_out=state.n_out.at[slot].set(0),
        finished=state.finished.at[slot].set(False),
        active=state.active.at[slot].set(True),
        drafts=state.drafts.at[slot].set(drafts.astype(jnp.int32)),
        draft_mask=state.draft_mask.at[slot].set(draft_mask),
        n_calls=state.n_calls.at[slot].set(0),
        accepted=state.accepted.at[slot].set(0),
        max_out=state.max_out.at[slot].set(jnp.int32(max_out)),
        stop_ids=state.stop_ids.at[slot].set(
            jnp.asarray(stop_ids, jnp.int32)),
        eff_dl=state.eff_dl.at[slot].set(jnp.int32(eff_dl)),
        eff_beams=state.eff_beams.at[slot].set(jnp.int32(eff_beams)),
    )


def release_slot(state: SessionState, slot) -> SessionState:
    """Evict a finished request; the slot's cache rows become garbage that
    the next ``reset_slot`` + cache prefill overwrite."""
    return state._replace(active=state.active.at[slot].set(False))


def paged_cache_entries(cache):
    """Flatten ``cache`` treating ``PagedKVCache`` nodes as leaves:
    (leaves, treedef, indices of the paged nodes). Works for any model
    cache pytree — the seq2seq ``{"self": ..., "cross": ...}`` dict (one
    paged node) and the decoder-only per-pattern-position tuple (one paged
    node per "attn" position, all sharing one page-id space)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        cache, is_leaf=lambda x: isinstance(x, PagedKVCache))
    idx = [i for i, leaf in enumerate(leaves)
           if isinstance(leaf, PagedKVCache)]
    return leaves, treedef, idx


def unmap_cache_rows(cache, rows):
    """Unmap block-table ``rows`` of every paged node in a model cache
    (``rows`` may be traced). Stale writes by the now-inactive rows fall
    through the -1 table entries into the trash page."""
    leaves, treedef, idx = paged_cache_entries(cache)
    for i in idx:
        sc = leaves[i]
        leaves[i] = dataclasses.replace(
            sc, block_tables=sc.block_tables.at[:, rows].set(-1))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def unmap_slot_pages(spec: SessionSpec, state: SessionState,
                     slot) -> SessionState:
    """Unmap a slot's block-table rows (paged caches; ``slot`` may be a
    traced scalar). Once unmapped, ``PageAllocator.reclaim`` returns the
    pages to the free list — an eviction or preemption frees the slot's
    whole footprint at once."""
    rows = slot * spec.rows_per_slot + jnp.arange(spec.rows_per_slot)
    return state._replace(cache=unmap_cache_rows(state.cache, rows))


# ---------------------------------------------------------------------------
# grouped sessions: per-mode slot groups sharing one cache and one step


class GroupedState(NamedTuple):
    """Session state partitioned into per-mode slot groups.

    ``groups[g]`` is a plain ``SessionState`` for group ``g``'s slots with
    ``cache=None`` — the model cache is held ONCE at the top level, covering
    every group's rows, so all groups share one paged page pool (or one
    dense row block) and one ``PageAllocator``. Group ``g`` owns the
    contiguous cache rows ``[offset_g, offset_g + specs[g].n_rows)`` in
    declaration order."""

    groups: tuple            # per-group SessionState (cache=None)
    cache: Any               # shared model cache over all groups' rows


def group_row_offsets(specs) -> list[int]:
    """Starting cache row of each group (+ total) in declaration order."""
    offs = [0]
    for spec in specs:
        offs.append(offs[-1] + spec.n_rows)
    return offs


def grouped_init_state(specs, cache) -> GroupedState:
    """All slots of all groups free. ``cache`` must have
    ``group_row_offsets(specs)[-1]`` batch rows and length >= the largest
    group's ``cache_len`` (groups with shorter draft windows simply never
    touch the tail blocks)."""
    return GroupedState(
        groups=tuple(init_state(spec, None) for spec in specs),
        cache=cache)


def grouped_step(specs, handle: DecoderHandle,
                 gstate: GroupedState) -> GroupedState:
    """ONE decode iteration for every slot of every group.

    Applies each group's pure ``session_step`` to its row slice of the
    shared cache, threading the (paged) pool through sequentially and
    merging each group's commits back. Group steps only write pages their
    own rows own (the allocator's private-window invariant), so the merge
    order is irrelevant to the result. Pure and shape-stable — jit it once
    per group tuple; admitting a request of one mode never retraces the
    other groups' math."""
    cache = gstate.cache
    out, lo = [], 0
    for spec, gs in zip(specs, gstate.groups):
        hi = lo + spec.n_rows
        st = gs._replace(cache=slice_rows(cache, lo, hi))
        st = session_step(spec, handle, st)
        cache = merge_rows(cache, st.cache, lo, hi)
        out.append(st._replace(cache=None))
        lo = hi
    return GroupedState(groups=tuple(out), cache=cache)


# ---------------------------------------------------------------------------
# paged-cache page allocation (host side)


class PoolExhausted(RuntimeError):
    """The page pool cannot satisfy a mapping request. The scheduler reacts
    by deferring admission or preempting the youngest resident request —
    exhaustion is a scheduling event, never a crash. ``group`` names the
    slot group whose row could not be mapped (None outside grouped
    sessions) so the scheduler can prefer an in-group preemption victim;
    ``shard`` names the data shard whose page-pool segment ran out (None
    outside sharded sessions) so preemption stays shard-local — evicting a
    resident of another shard would free the wrong pool segment."""

    def __init__(self, msg: str, group=None, shard=None):
        super().__init__(msg)
        self.group = group
        self.shard = shard


class PageAllocator:
    """Host-side free-list allocator + block-table maintenance for a session
    whose model cache uses a ``PagedKVCache`` self-attention cache.

    The jitted session step never allocates: between steps the host

      1. ``reclaim(state)`` — recomputes page reference counts from the
         (tiny) block tables and returns every unreferenced page to the
         free list.  Beam reorder / winner sync inside the step alias and
         orphan pages freely; this pass is the single garbage collector.
      2. ``prepare_step(state)`` — walks every live row's write window
         ``[pos, pos + DL]`` and restores the invariant the step's writes
         rely on: each window block is mapped to a page owned by exactly
         one row.  Shared boundary pages (aliased by winner sync or beam
         gather) are split copy-on-write — the partially committed boundary
         block is copied, fully-stale blocks just get fresh empty pages.
         Unmapped blocks (frontier growth, fresh admissions) are mapped
         lazily, so a short request only ever holds the pages its tokens
         actually occupy.

    Page 0 is the reserved trash page (writes with no mapped target land
    there, masked by stored position -1) and is never allocated. The pool
    must at least cover one slot's worst case so the oldest resident request
    can always run to completion — that bound makes deferral + preemption a
    complete (deadlock-free) admission policy.
    """

    def __init__(self, spec, *, n_pages: int, page_size: int,
                 row_lens: dict | None = None,
                 prefill_blocks: dict | None = None):
        # ``spec``: one SessionSpec, or an ordered {group_key: SessionSpec}
        # mapping for a grouped session (declaration order == row order,
        # matching GroupedState.groups)
        # ``row_lens``: per-group logical row length when it exceeds
        # spec.cache_len — decoder-only rows also hold the prompt
        # (row_len = max_src + cache_len); default spec.cache_len.
        # ``prefill_blocks``: per-group worst-case prompt blocks a chunked
        # prefill maps into ONE row before the slot's siblings alias them
        # (0 = monolithic admission writes no prompt into the paged cache,
        # the seq2seq case).
        self.groups: dict = ({None: spec} if isinstance(spec, SessionSpec)
                             else dict(spec))
        self.spec = next(iter(self.groups.values()))   # primary (legacy API)
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # linear block space: the allocator does not model the sliding-window
        # block ring of init_paged_kv_cache (callers must gate on
        # cfg.sliding_window == 0, as StreamingEngine does)
        row_lens = row_lens or {}
        self._blocks = {k: -(-int(row_lens.get(k, s.cache_len))
                             // self.page_size)
                        for k, s in self.groups.items()}
        self._prefill_blocks = {k: int((prefill_blocks or {}).get(k, 0))
                                for k in self.groups}
        self.n_blocks = max(self._blocks.values())
        # one slot's worst case: prompt pages are mapped once and shared by
        # the slot's rows (only the draft-boundary page is ever
        # copy-on-write-split per row), so a chunked-prefill group needs
        # prefill_blocks + rows * (decode blocks + the split boundary). A
        # single-row slot never shares (no copy-on-write transient), and
        # monolithic groups write no prompt: both keep rows * blocks.
        self._slot_worst = {}
        for k, s in self.groups.items():
            pb = self._prefill_blocks[k]
            if pb and s.rows_per_slot > 1:
                need = pb + s.rows_per_slot * (
                    -(-s.cache_len // self.page_size) + 1)
            else:
                need = s.rows_per_slot * self._blocks[k]
            self._slot_worst[k] = need
        need_one_slot = max(self._slot_worst.values())
        if self.n_pages - 1 < need_one_slot:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one slot's worst case "
                f"({need_one_slot} pages of {page_size} tokens + trash page); "
                f"no admission policy can make progress")
        self._free: list[int] = list(range(self.n_pages - 1, TRASH_PAGE, -1))
        self._used: set[int] = set()
        # cache rows treated as live in every scan even while their slot is
        # still inactive: a chunked prefill maps pages into a slot whose
        # SessionState stays inactive until the prompt is fully written
        self._pinned_rows: set[int] = set()
        self.peak_pages = 0

    # ---------------------------------------------------------------- state
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._used)

    def window_blocks(self, pos: int, group=None) -> range:
        """Logical blocks the next step writes for a ``group`` row at
        position ``pos`` (tokens land at pos .. pos + DL)."""
        if group is None:
            group = next(iter(self.groups))
        ps = self.page_size
        lo = pos // ps
        hi = min((pos + self.groups[group].draft_len) // ps,
                 self._blocks[group] - 1)
        return range(lo, hi + 1)

    def admit_pages_for(self, group=None) -> int:
        """Pages a fresh ``group`` admission maps on its first step (window
        at pos 0), plus one window of headroom so resident rows'
        copy-on-write splits do not immediately preempt the newcomer.
        Chunked-prefill groups add their worst-case prompt blocks (mapped
        into one row before decode starts). Clamped to one slot's worst
        case — the bound the constructor validates the pool against — so
        an empty pool can always admit (no admission deadlock)."""
        if group is None:
            group = next(iter(self.groups))
        per_row = len(self.window_blocks(0, group))
        want = self._prefill_blocks[group] + (
            self.groups[group].rows_per_slot * min(
                2 * per_row, self._blocks[group]))
        return min(want, self._slot_worst[group])

    @property
    def admit_pages(self) -> int:
        return self.admit_pages_for()

    def _alloc(self) -> int:
        if not self._free:
            raise PoolExhausted(f"page pool exhausted "
                                f"({self.used_pages}/{self.n_pages - 1} used)")
        p = self._free.pop()
        self._used.add(p)
        self.peak_pages = max(self.peak_pages, len(self._used))
        return p

    # ------------------------------------------------------------- host ops
    def _tables(self, state: SessionState):
        """(paged leaves, treedef, paged indices, host table copy). Every
        paged node of the cache carries an identical block table by
        construction (layer copies along axis 0, one node per attention
        pattern position sharing the page-id space); read one, update all.
        The np.array is a host copy — prepare_step mutates it as its
        worklist."""
        leaves, treedef, idx = paged_cache_entries(state.cache)
        if not idx:
            raise TypeError("PageAllocator requires a PagedKVCache node in "
                            "the model cache (init_cache(..., "
                            "paged=(n_pages, ps)))")
        return leaves, treedef, idx, np.array(leaves[idx[0]].block_tables[0])

    def _rebuild(self, state, leaves, treedef, idx, *, tables=None,
                 copy_src=None, copy_dst=None, fresh=None):
        """Apply table/pos/page-copy updates to EVERY paged node and return
        the state with the rebuilt cache. ``tables`` is a callable applied
        per node (nodes share page ids but own distinct pools)."""
        for i in idx:
            sc = leaves[i]
            kw = {}
            if tables is not None:
                kw["block_tables"] = tables(sc.block_tables)
            pos_pool = sc.pos
            if fresh is not None:
                pos_pool = pos_pool.at[:, fresh].set(-1)
            if copy_dst is not None:
                kw["k_pool"] = sc.k_pool.at[:, copy_dst].set(
                    sc.k_pool[:, copy_src])
                kw["v_pool"] = sc.v_pool.at[:, copy_dst].set(
                    sc.v_pool[:, copy_src])
                pos_pool = pos_pool.at[:, copy_dst].set(pos_pool[:, copy_src])
            kw["pos"] = pos_pool
            leaves[i] = dataclasses.replace(sc, **kw)
        cache = jax.tree_util.tree_unflatten(treedef, leaves)
        return state._replace(cache=cache)

    def _group_views(self, state):
        """(group key, spec, row offset, pos (S,K), active (S,)) per group.
        Accepts a plain ``SessionState`` (single group) or ``GroupedState``
        (one view per group, in the shared declaration/row order)."""
        if isinstance(state, GroupedState):
            if len(state.groups) != len(self.groups):
                raise ValueError(
                    f"allocator has {len(self.groups)} group spec(s) but "
                    f"the state has {len(state.groups)}")
            lo = 0
            for (key, spec), gs in zip(self.groups.items(), state.groups):
                yield key, spec, lo, np.asarray(gs.pos), np.asarray(gs.active)
                lo += spec.n_rows
        else:
            key = next(iter(self.groups))
            yield (key, self.groups[key], 0, np.asarray(state.pos),
                   np.asarray(state.active))

    def _scan(self, state):
        """ONE device readback feeding reclaim, admission accounting, and
        the prepare walk: (paged-leaf bundle, tables, group views,
        refcounts). As a side effect, returns every unreferenced page to
        the free list (rows of released slots must already be unmapped —
        ``unmap_slot_pages``). Pinned rows (mid-prefill slots, inactive by
        design) count as live."""
        leaves, treedef, idx, bt = self._tables(state)
        views = list(self._group_views(state))
        rows = [np.fromiter(sorted(self._pinned_rows), np.int64)]
        for _, spec, lo, _, active in views:
            rps = spec.rows_per_slot
            rows.append((lo + np.flatnonzero(active)[:, None] * rps
                         + np.arange(rps)[None, :]).reshape(-1))
        live = bt[np.concatenate(rows)]
        refs = np.bincount(live[live >= 0].ravel(), minlength=self.n_pages)
        for p in [p for p in self._used if refs[p] == 0]:
            self._used.remove(p)
            self._free.append(p)
        return (leaves, treedef, idx), bt, views, refs

    # -------------------------------------------------- chunked prefill ops
    def pin_rows(self, rows) -> None:
        """Mark cache rows live while their slot is still inactive (a
        chunked prefill in flight); unpin when the slot activates or its
        request is preempted/released."""
        self._pinned_rows.update(int(r) for r in rows)

    def unpin_rows(self, rows) -> None:
        self._pinned_rows.difference_update(int(r) for r in rows)

    def map_prefill(self, state, row: int, blocks, group=None):
        """Map fresh pages for logical ``blocks`` of cache row ``row`` so
        the next prefill chunk can write straight into the slot's block
        table. Already-mapped blocks are skipped (the chunk boundary block
        stays). Raises ``PoolExhausted`` on pool pressure — the scheduler
        preempts and retries; pages allocated before the raise are
        unreferenced and return to the free list on the next scan."""
        leaves, treedef, idx, bt = self._tables(state)
        set_j, set_p = [], []
        for j in blocks:
            if bt[row, j] >= 0:
                continue
            try:
                set_p.append(self._alloc())
            except PoolExhausted as e:
                e.group = group
                raise
            set_j.append(j)
        if not set_j:
            return state
        js = np.asarray(set_j)
        ps_ids = np.asarray(set_p, np.int32)
        return self._rebuild(
            state, leaves, treedef, idx,
            tables=lambda t: t.at[:, row, js].set(ps_ids), fresh=ps_ids)

    def reclaim(self, state) -> None:
        """Return every page unreferenced by a live row to the free list."""
        self._scan(state)

    def _unmapped_window_blocks(self, bt, views) -> int:
        """Live window blocks no page is mapped to yet — what the next
        ``prepare_step`` must allocate before any new admission's share."""
        n = 0
        for key, spec, lo, pos, active in views:
            K, N_d = spec.n_beams, spec.n_drafts
            for s in np.flatnonzero(active):
                for k in range(K):
                    window = self.window_blocks(int(pos[s, k]), key)
                    for d in range(N_d):
                        r = lo + (s * K + k) * N_d + d
                        n += sum(1 for j in window if bt[r, j] < 0)
        return n

    def can_admit(self, state, group=None) -> bool:
        """Gate a ``group`` admission on free pages, net of the pages
        already-resident rows still need mapped (a burst of admissions in
        one scheduler cycle books its pages here — lazily-mapped slots are
        not double-counted as free)."""
        _, bt, views, _ = self._scan(state)
        pending = self._unmapped_window_blocks(bt, views)
        return self.free_pages - pending >= self.admit_pages_for(group)

    def prepare_step(self, state):
        """Reclaim orphans, then map/privatize every live row's write window
        (lazy growth + copy-on-write at the draft boundary). Returns the
        updated state; raises ``PoolExhausted`` (allocator self-heals via the
        next ``reclaim``) when the pool cannot cover the windows."""
        bundle, bt, views, refs = self._scan(state)
        ps = self.page_size

        set_r: list[int] = []; set_j: list[int] = []; set_p: list[int] = []
        fresh: list[int] = []                             # pos := -1
        copy_src: list[int] = []; copy_dst: list[int] = []
        for key, spec, lo, pos, active in views:
            K, N_d = spec.n_beams, spec.n_drafts
            for s in np.flatnonzero(active):
                for k in range(K):
                    p_row = int(pos[s, k])
                    window = self.window_blocks(p_row, key)
                    for d in range(N_d):
                        r = lo + (s * K + k) * N_d + d
                        for j in window:
                            cur = int(bt[r, j])
                            if cur >= 0 and refs[cur] == 1:
                                continue                  # already private
                            try:
                                new = self._alloc()
                            except PoolExhausted as e:
                                e.group = key  # in-group preemption hint
                                raise
                            if cur >= 0:
                                refs[cur] -= 1
                            refs[new] = 1
                            if cur >= 0 and j == window[0] and p_row % ps:
                                # boundary block holds committed tokens: copy
                                # the whole page — entries >= pos are stale
                                # draft slots the next write pass overwrites
                                # pre-read
                                copy_src.append(cur)
                                copy_dst.append(new)
                            else:
                                fresh.append(new)
                            bt[r, j] = new
                            set_r.append(r); set_j.append(j)
                            set_p.append(new)

        if not (set_r or fresh or copy_dst):
            return state
        leaves, treedef, idx = bundle
        tables_fn = None
        if set_r:
            r_ix, j_ix = np.asarray(set_r), np.asarray(set_j)
            p_ix = np.asarray(set_p, np.int32)
            tables_fn = lambda t: t.at[:, r_ix, j_ix].set(p_ix)
        return self._rebuild(
            state, leaves, treedef, idx, tables=tables_fn,
            fresh=np.asarray(fresh) if fresh else None,
            copy_src=np.asarray(copy_src) if copy_dst else None,
            copy_dst=np.asarray(copy_dst) if copy_dst else None)

    # ------------------------------------------------------------ debugging
    def check(self) -> None:
        """Allocator invariants (exercised by the hypothesis tests)."""
        free = self._free
        assert len(set(free)) == len(free), "duplicate pages in free list"
        assert not (set(free) & self._used), "page both free and allocated"
        assert TRASH_PAGE not in self._used and TRASH_PAGE not in free
        assert set(free) | self._used == set(range(1, self.n_pages)), \
            "page leaked"


class ShardedPageAllocator(PageAllocator):
    """Per-shard view over ONE page pool partitioned across a device mesh's
    data axis: shard ``s`` owns the contiguous page segment
    ``[s * pages_per_shard, (s + 1) * pages_per_shard)``; the reserved
    trash page 0 sits inside shard 0's segment and is never allocated.

    Host accounting stays global — since the fused megastep this class
    (like its parent) does admission sizing and pinning only, never the
    allocation itself (``device_page_plan`` allocates, segment-locally
    when given the shard map). What the subclass adds is the shard
    geometry the engine's placement / admission / preemption logic keys
    on: which shard owns a page, each shard's usable capacity, per-shard
    peak tracking, and the validation that EVERY shard's segment covers
    one slot's worst case — the bound that makes per-shard deferral plus
    shard-local preemption a complete (deadlock-free) policy, exactly as
    the global bound does for the single-device pool."""

    def __init__(self, spec, *, n_pages: int, page_size: int, n_shards: int,
                 row_lens: dict | None = None,
                 prefill_blocks: dict | None = None):
        super().__init__(spec, n_pages=n_pages, page_size=page_size,
                         row_lens=row_lens, prefill_blocks=prefill_blocks)
        self.n_shards = int(n_shards)
        if self.n_shards < 1:
            raise ValueError(f"n_shards={n_shards} must be >= 1")
        if self.n_pages % self.n_shards:
            raise ValueError(
                f"n_pages={n_pages} must divide evenly across "
                f"{self.n_shards} data shards (contiguous equal page "
                f"segments are what lets the device plan allocate "
                f"shard-locally with one reshape)")
        self.pages_per_shard = self.n_pages // self.n_shards
        need_one_slot = max(self._slot_worst.values())
        if self.shard_capacity(0) < need_one_slot:
            raise ValueError(
                f"n_pages={n_pages} over {self.n_shards} shards leaves "
                f"{self.shard_capacity(0)} usable pages in shard 0, below "
                f"one slot's worst case ({need_one_slot}); shard-local "
                f"preemption could not make progress")
        self.peak_pages_by_shard = [0] * self.n_shards

    def shard_of_page(self, page: int) -> int:
        """Owning shard of a page id (the radix-affinity feed: a committed
        prefix chain's pages all come from its slot's shard segment)."""
        return int(page) // self.pages_per_shard

    def shard_capacity(self, shard: int) -> int:
        """Usable (allocatable) pages in a shard's segment — shard 0
        donates one page to the trash."""
        return self.pages_per_shard - (1 if shard == 0 else 0)

    def note_peak(self, free_by_shard) -> None:
        """Fold one bundle's per-shard free counts into the per-shard
        page high-water marks (the bench's pool-balance feed)."""
        for s, free in enumerate(free_by_shard):
            used = self.shard_capacity(s) - int(free)
            if used > self.peak_pages_by_shard[s]:
                self.peak_pages_by_shard[s] = used


# ---------------------------------------------------------------------------
# cross-request prefix page sharing: radix tree over committed pages


class RadixNode:
    """One committed page of prompt tokens in the prefix tree. The node
    owns exactly one page and one *index cell* — a (row, block) slot in the
    reserved index rows of the block table whose reference keeps the page
    allocated on device while no request aliases it."""

    __slots__ = ("key", "page", "parent", "children", "cell", "active",
                 "last_used", "depth")

    def __init__(self, key, page, parent, cell, depth):
        self.key = key              # tuple of page_size token ids
        self.page = int(page)
        self.parent = parent
        self.children: dict = {}
        self.cell = cell            # (index row, block) holding the ref
        self.active = 0             # resident requests aliasing this page
        self.last_used = 0          # LRU stamp (monotone counter)
        self.depth = depth


class RadixPageCache:
    """Host-side radix (prefix) tree over committed prompt pages.

    RadixAttention-style cross-request reuse (SGLang) for the paged KV
    cache: a request's prompt is keyed in ``page_size``-token chunks; on
    admission the engine matches the prompt against this tree, aliases the
    matched pages into the new slot's block table, and prefills only the
    unmatched suffix. A node's page stays allocated — visible to both the
    host allocator's scan and the device page plan's refcounts — through
    its *index cell*: one entry in the reserved index rows of the shared
    block table. Clearing the cell is the whole eviction; the page then
    reads as unreferenced and returns to the pool on the next reclaim.

    Shared pages are CoW-safe for free: the index-cell reference makes
    ``refs > win_refs`` for any decode window touching a shared page, so
    the device plan (and the host walk) never elect it as a keeper — a
    writer always copies first.

    The tree itself is pure host bookkeeping; all device work (writing /
    clearing cells) is done by the engine through the fixed-shape helpers
    below so the megastep stays one dispatch."""

    def __init__(self, page_size: int, n_cells: int):
        self.page_size = int(page_size)
        self.n_cells = int(n_cells)
        self.root = RadixNode(None, -1, None, None, 0)
        self._free_cells = list(range(n_cells - 1, -1, -1))
        self._nodes_by_cell: dict[int, RadixNode] = {}
        self._clock = 0
        # stats (the bench's prefix_hit_rate feed)
        self.lookups = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.inserted = 0
        self.evicted = 0

    def __len__(self) -> int:
        return len(self._nodes_by_cell)

    @property
    def free_cells(self) -> int:
        return len(self._free_cells)

    def _keys(self, tokens) -> list[tuple]:
        ps = self.page_size
        toks = [int(t) for t in tokens]
        return [tuple(toks[i:i + ps])
                for i in range(0, len(toks) - ps + 1, ps)]

    def match(self, tokens) -> list[RadixNode]:
        """Longest-prefix match of ``tokens`` against the tree, in whole
        pages. Returns the matched node chain root-first (possibly empty);
        records hit-rate stats."""
        self._clock += 1
        self.lookups += 1
        self.lookup_tokens += len(tokens)
        chain, node = [], self.root
        for key in self._keys(tokens):
            nxt = node.children.get(key)
            if nxt is None:
                break
            nxt.last_used = self._clock
            chain.append(nxt)
            node = nxt
        self.hit_tokens += len(chain) * self.page_size
        return chain

    def peek(self, tokens) -> list[RadixNode]:
        """``match`` without side effects: the longest matched chain,
        touching neither the LRU clock nor the hit-rate stats. The
        engine's shard-placement probe — placement may still route the
        request elsewhere (or shed it), so a peek must not count as a
        lookup or refresh recency."""
        chain, node = [], self.root
        for key in self._keys(tokens):
            nxt = node.children.get(key)
            if nxt is None:
                break
            chain.append(nxt)
            node = nxt
        return chain

    def insert(self, tokens, pages, depth0: int = 0) -> list[RadixNode]:
        """Extend the tree with ``tokens`` (full pages only) mapped to
        ``pages`` (one page id per key chunk, the committed prompt pages of
        the finishing prefill). ``depth0`` skips chunks already matched at
        admission. Returns the NEW nodes (the engine writes their index
        cells); chunks already present are refreshed, not replaced. Runs
        out of cells -> stops inserting (the tree is a cache, not a
        ledger)."""
        self._clock += 1
        keys = self._keys(tokens)
        node = self.root
        for key in keys[:depth0]:
            nxt = node.children.get(key)
            if nxt is None:
                return []          # matched chain was evicted mid-flight
            nxt.last_used = self._clock
            node = nxt
        new: list[RadixNode] = []
        for d, key in enumerate(keys[depth0:], start=depth0):
            nxt = node.children.get(key)
            if nxt is None:
                if not self._free_cells:
                    break
                cell = self._free_cells.pop()
                nxt = RadixNode(key, int(pages[d]), node, cell, d + 1)
                node.children[key] = nxt
                self._nodes_by_cell[cell] = nxt
                new.append(nxt)
                self.inserted += 1
            nxt.last_used = self._clock
            node = nxt
        return new

    def acquire(self, chain) -> None:
        for node in chain:
            node.active += 1

    def release(self, chain) -> None:
        for node in chain:
            node.active -= 1
            assert node.active >= 0, "radix node released below zero"

    def _drop(self, node: RadixNode) -> int:
        """Unlink one leaf node and recycle its cell; returns the cell."""
        assert not node.children and node.active == 0
        del node.parent.children[node.key]
        del self._nodes_by_cell[node.cell]
        self._free_cells.append(node.cell)
        self.evicted += 1
        return node.cell

    def evict_lru(self, n: int, where=None) -> list[tuple[int, int]]:
        """Evict up to ``n`` least-recently-used inactive LEAF nodes
        (leaf-first keeps the tree prefix-closed). Returns the
        ``(cell, page)`` pairs whose index cells the engine must clear —
        the pages become unreferenced once no resident row aliases them.
        ``where`` narrows the victim pool (sharded engines reclaim from
        the exhausted page-pool shard first — evicting another shard's
        nodes frees pages the short shard cannot use)."""
        out: list[tuple[int, int]] = []
        while len(out) < n:
            victims = [nd for nd in self._nodes_by_cell.values()
                       if not nd.children and nd.active == 0
                       and (where is None or where(nd))]
            if not victims:
                break
            victims.sort(key=lambda nd: nd.last_used)
            for nd in victims:
                if len(out) >= n:
                    break
                out.append((self._drop(nd), nd.page))
        return out

    def drop_subtree(self, node: RadixNode) -> list[tuple[int, int]]:
        """Remove ``node`` and every descendant whose whole chain is
        inactive (a pruned search subtree releases its page subtree at
        once). Nodes still aliased by a resident request are kept — their
        pages stay live through the rows that alias them. Returns the
        cleared ``(cell, page)`` pairs."""
        out: list[tuple[int, int]] = []

        def walk(nd: RadixNode) -> bool:
            keep = nd.active > 0
            for child in list(nd.children.values()):
                if not walk(child):
                    keep = True
            if not keep:
                out.append((self._drop(nd), nd.page))
            return not keep

        walk(node)
        return out

    def check(self) -> None:
        """Tree invariants (exercised by the hypothesis tests)."""
        assert len(set(self._free_cells)) == len(self._free_cells)
        assert not (set(self._free_cells) & set(self._nodes_by_cell))
        assert (set(self._free_cells) | set(self._nodes_by_cell)
                == set(range(self.n_cells))), "index cell leaked"

        def walk(nd):
            for key, child in nd.children.items():
                assert child.parent is nd and child.key == key
                assert self._nodes_by_cell.get(child.cell) is child
                assert child.active >= 0
                walk(child)

        walk(self.root)


def radix_cell_coords(n_rows: int, n_blocks: int, cells):
    """Map flat index-cell ids to (index row, block) coordinates. Index
    rows live at rows >= ``n_rows`` (the session's group rows) in the
    block table; each holds ``n_blocks`` cells."""
    cells = np.asarray(list(cells), np.int64)
    return n_rows + cells // n_blocks, cells % n_blocks


def write_index_cells(cache, rows, blocks, pages, count):
    """Jit-side: scatter ``pages`` into the reserved index rows of every
    paged node's block table — the retain that keeps a radix node's page
    allocated. Fixed-shape: ``rows``/``blocks``/``pages`` are padded
    arrays, lanes >= ``count`` are dropped (row index past the table)."""
    leaves, treedef, idx = paged_cache_entries(cache)
    n_rows_tab = leaves[idx[0]].block_tables.shape[1]
    lane = jnp.arange(rows.shape[0])
    rr = jnp.where(lane < count, rows, n_rows_tab)
    for i in idx:
        sc = leaves[i]
        leaves[i] = dataclasses.replace(
            sc, block_tables=sc.block_tables.at[:, rr, blocks].set(
                pages, mode="drop"))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def clear_index_cells(cache, rows, blocks, count):
    """Jit-side: reset index cells to -1 (radix eviction / subtree drop);
    the pages they referenced become reclaimable once no live row aliases
    them. Same fixed-shape lane convention as ``write_index_cells``."""
    leaves, treedef, idx = paged_cache_entries(cache)
    n_rows_tab = leaves[idx[0]].block_tables.shape[1]
    lane = jnp.arange(rows.shape[0])
    rr = jnp.where(lane < count, rows, n_rows_tab)
    for i in idx:
        sc = leaves[i]
        leaves[i] = dataclasses.replace(
            sc, block_tables=sc.block_tables.at[:, rr, blocks].set(
                -1, mode="drop"))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def alias_prefix_pages(cache, row0, pages, count):
    """Jit-side: write a matched prefix-page chain into the leading blocks
    of cache row ``row0`` (the slot's prefill row) — the suffix-only
    admission's aliasing step. ``pages`` is a fixed-shape padded (B,)
    array; blocks >= ``count`` keep their current (unmapped) entries, so
    the suffix prefill maps them fresh."""
    leaves, treedef, idx = paged_cache_entries(cache)
    n_rows_tab = leaves[idx[0]].block_tables.shape[1]
    blocks = jnp.arange(pages.shape[0])
    rr = jnp.where(blocks < count, row0, n_rows_tab)
    for i in idx:
        sc = leaves[i]
        leaves[i] = dataclasses.replace(
            sc, block_tables=sc.block_tables.at[:, rr, blocks].set(
                pages, mode="drop"))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def read_row_pages(cache, rows0, n_blocks: int) -> jnp.ndarray:
    """Jit-side: the leading ``n_blocks`` block-table entries of the
    given rows — the megastep bundle's committed-prompt-page feed (the
    host learns which pages a finished prefill wrote without an extra
    readback)."""
    leaves, _, idx = paged_cache_entries(cache)
    bt = leaves[idx[0]].block_tables[0]
    return bt[jnp.asarray(rows0), :n_blocks]


# ---------------------------------------------------------------------------
# paged-cache page allocation (device side — the fused megastep's free stack)


class DevicePagePlan(NamedTuple):
    """One iteration's page maintenance, computed ON DEVICE inside the
    fused megastep (``StreamingEngine``): the same lazy-growth +
    copy-on-write walk ``PageAllocator.prepare_step`` and ``map_prefill``
    do on the host, restated as fixed-shape lane arrays over the block
    tables. ``exhausted`` is the device flag the scheduler syncs on —
    allocation is all-or-nothing, so an exhausted iteration applies
    nothing and the host preempts + replays exactly as before. All lane
    arrays share one flat length L (decode windows of every group, then
    prefill chunk lanes)."""

    exhausted: jnp.ndarray       # () bool — some segment overflows (global:
                                 # any shard short => whole step replays)
    n_free: jnp.ndarray          # () int32 free pages before allocation
    need_by_group: jnp.ndarray   # (G,) int32 pages each group's lanes need
    rows: jnp.ndarray            # (L,) int32 lane cache row
    blocks: jnp.ndarray          # (L,) int32 lane logical block
    need: jnp.ndarray            # (L,) bool lane allocates a page
    copy: jnp.ndarray            # (L,) bool draft-boundary copy-on-write
    cur: jnp.ndarray             # (L,) int32 current page (-1 = unmapped)
    new: jnp.ndarray             # (L,) int32 allocated page (if ``need``)
    # sharded sessions only (None on a single-segment pool): per-data-shard
    # accounting over the contiguous page segments
    need_by_shard: jnp.ndarray | None = None     # (n_shards,) int32
    n_free_by_shard: jnp.ndarray | None = None   # (n_shards,) int32
    exhausted_by_shard: jnp.ndarray | None = None  # (n_shards,) bool


def _page_refs(bt: jnp.ndarray, n_pages: int) -> jnp.ndarray:
    """(n_pages,) reference counts over one block table. Released and
    recycled rows are always unmapped (``release``/``_clean_rows``), so
    every mapped entry belongs to a live — active or mid-prefill — row:
    the device needs no pinned-row side channel."""
    return jnp.zeros((n_pages,), jnp.int32).at[
        jnp.where(bt >= 0, bt, n_pages).reshape(-1)].add(1, mode="drop")


def device_free_pages(cache, n_pages: int) -> jnp.ndarray:
    """() int32 — pages no live row references (the mirrored-counter feed
    for host-side admission accounting)."""
    leaves, _, idx = paged_cache_entries(cache)
    bt = leaves[idx[0]].block_tables[0]
    refs = _page_refs(bt, n_pages)
    return jnp.sum(((refs == 0)
                    & (jnp.arange(n_pages) != TRASH_PAGE)).astype(jnp.int32))


def device_free_pages_by_shard(cache, n_pages: int,
                               n_shards: int) -> jnp.ndarray:
    """(n_shards,) int32 — free pages per contiguous shard segment (shard
    ``s`` owns pages ``[s * pps, (s + 1) * pps)``, trash page inside shard
    0). The per-shard mirrored-counter feed for sharded admission."""
    leaves, _, idx = paged_cache_entries(cache)
    bt = leaves[idx[0]].block_tables[0]
    refs = _page_refs(bt, n_pages)
    free = (refs == 0) & (jnp.arange(n_pages) != TRASH_PAGE)
    return jnp.sum(free.reshape(n_shards, -1).astype(jnp.int32), axis=1)


def device_page_plan(specs, blocks, page_size: int, n_pages: int,
                     gstate: GroupedState, prefill=None,
                     shards=None) -> DevicePagePlan:
    """Plan this iteration's page maintenance on device.

    ``specs``/``blocks`` are static (the allocator's per-group logical
    block counts); ``prefill`` is None or a per-group tuple of
    ``(rows0, pos0, n_valid, chunk)`` describing the chunk each group's
    slots write this iteration (``rows0``/``chunk`` static, the rest
    traced; ``n_valid == 0`` lanes are idle).

    The copy-on-write rule replicates the host walk's outcome without its
    sequential refcount mutation: a lane keeps its current page iff no
    out-of-window row references it (``refs == win_refs``) AND the lane is
    the highest-row in-window referencer (the host walk visits rows in
    ascending order, so the LAST visitor sees refs == 1 and keeps the
    page). Fresh pages come off an ascending free stack — page identity
    never affects tokens (attention masks on stored positions), only the
    count matters for accounting.

    ``shards`` is None (one global free stack, the single-device path —
    bit-identical to before sharding existed) or ``(n_shards, row_shard)``
    with ``row_shard`` a host (n_rows_tab,) array mapping each cache row
    to its owning data shard. Sharded allocation is SEGMENT-LOCAL: shard
    ``s`` owns the contiguous pages ``[s * pps, (s + 1) * pps)`` and a
    lane draws from its row's shard stack only, so one shard's burst can
    never consume another shard's pool. Exhaustion is still all-or-nothing
    and GLOBAL (any short segment replays the whole step) — the host
    preempts a victim inside the overflowing shard and replays, keeping
    the deterministic preempt-and-replay contract per shard."""
    ps, P = int(page_size), int(n_pages)

    leaves, _, idx = paged_cache_entries(gstate.cache)
    bt = leaves[idx[0]].block_tables[0]
    n_rows_tab, n_blocks = bt.shape
    refs = _page_refs(bt, P)
    free = (refs == 0) & (jnp.arange(P) != TRASH_PAGE)
    n_free = jnp.sum(free.astype(jnp.int32))
    if shards is None:
        rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        stack = jnp.full((P,), P, jnp.int32).at[
            jnp.where(free, rank, P)].set(jnp.arange(P, dtype=jnp.int32),
                                          mode="drop")
    else:
        n_shards, row_shard = shards
        n_shards = int(n_shards)
        if P % n_shards:
            raise ValueError(f"n_pages={P} must divide across "
                             f"{n_shards} shards")
        pps = P // n_shards
        row_shard = jnp.asarray(np.asarray(row_shard), jnp.int32)
        free_sh = free.reshape(n_shards, pps)
        n_free_sh = jnp.sum(free_sh.astype(jnp.int32), axis=1)
        rank_sh = jnp.cumsum(free_sh.astype(jnp.int32), axis=1) - 1
        srow = jnp.broadcast_to(
            jnp.arange(n_shards, dtype=jnp.int32)[:, None], (n_shards, pps))
        # per-shard ascending free stacks over the shard's own segment
        stack_sh = jnp.full((n_shards, pps), P, jnp.int32).at[
            srow, jnp.where(free_sh, rank_sh, pps)].set(
            jnp.arange(P, dtype=jnp.int32).reshape(n_shards, pps),
            mode="drop")

    offs = group_row_offsets(specs)
    lane_r, lane_j, lane_valid, lane_pos, lane_w0, lane_gi = \
        [], [], [], [], [], []
    for gi, (spec, gs) in enumerate(zip(specs, gstate.groups)):
        lo = offs[gi]
        K, N_d, DL = spec.n_beams, spec.n_drafts, spec.draft_len
        nR, W = spec.n_rows, DL // ps + 2
        rg = jnp.arange(nR, dtype=jnp.int32)
        s, k = rg // (K * N_d), (rg // N_d) % K
        pos_r = gs.pos[s, k]
        act = gs.active[s]
        w = jnp.arange(W, dtype=jnp.int32)
        j = pos_r[:, None] // ps + w[None, :]
        hi = jnp.minimum((pos_r + DL) // ps, blocks[gi] - 1)
        lane_r.append(jnp.broadcast_to((lo + rg)[:, None],
                                       (nR, W)).reshape(-1))
        lane_j.append(j.reshape(-1))
        lane_valid.append((act[:, None] & (j <= hi[:, None])).reshape(-1))
        lane_pos.append(jnp.broadcast_to(pos_r[:, None], (nR, W)).reshape(-1))
        lane_w0.append(jnp.broadcast_to(w[None, :] == 0, (nR, W)).reshape(-1))
        lane_gi.append(jnp.full((nR * W,), gi, jnp.int32))
    r = jnp.concatenate(lane_r)
    jb = jnp.concatenate(lane_j)
    valid = jnp.concatenate(lane_valid)
    posl = jnp.concatenate(lane_pos)
    w0 = jnp.concatenate(lane_w0)
    gsel = jnp.concatenate(lane_gi)

    cur = jnp.where(valid, bt[r, jnp.clip(jb, 0, n_blocks - 1)], -1)
    vc = valid & (cur >= 0)
    safe_cur = jnp.where(vc, cur, P)
    win_refs = jnp.zeros((P,), jnp.int32).at[safe_cur].add(1, mode="drop")
    keeper = jnp.full((P,), -1, jnp.int32).at[safe_cur].max(
        jnp.where(vc, r, -1), mode="drop")
    cc = jnp.clip(cur, 0, P - 1)
    keep = vc & (refs[cc] == win_refs[cc]) & (r == keeper[cc])
    need = valid & ~keep
    copy = need & vc & w0 & (posl % ps != 0)

    if prefill is not None:
        # frontier growth for this iteration's prompt chunks (map_prefill's
        # skip-already-mapped semantics): always fresh pages, row 0 only
        pr, pj, pn, pg = [r], [jb], [need], [gsel]
        pc, pu = [copy], [cur]
        for gi, pf in enumerate(prefill):
            rows0, pos0, n_valid, chunk = pf
            CB = -(-int(chunk) // ps) + 1
            c = jnp.arange(CB, dtype=jnp.int32)
            j = pos0[:, None] // ps + c[None, :]
            hi = (pos0 + jnp.maximum(n_valid, 1) - 1) // ps
            r0 = jnp.asarray(rows0, jnp.int32)
            mapped = bt[r0[:, None], jnp.clip(j, 0, n_blocks - 1)] >= 0
            v = (n_valid[:, None] > 0) & (j <= hi[:, None]) & ~mapped
            L = v.size
            pr.append(jnp.broadcast_to(r0[:, None], j.shape).reshape(-1))
            pj.append(j.reshape(-1))
            pn.append(v.reshape(-1))
            pc.append(jnp.zeros((L,), bool))
            pu.append(jnp.full((L,), -1, jnp.int32))
            pg.append(jnp.full((L,), gi, jnp.int32))
        r, jb = jnp.concatenate(pr), jnp.concatenate(pj)
        need, copy = jnp.concatenate(pn), jnp.concatenate(pc)
        cur, gsel = jnp.concatenate(pu), jnp.concatenate(pg)

    need_by_group = jnp.zeros((len(specs),), jnp.int32).at[gsel].add(
        need.astype(jnp.int32))
    if shards is None:
        ni = jnp.cumsum(need.astype(jnp.int32)) - 1
        new = stack[jnp.clip(jnp.where(need, ni, 0), 0, P - 1)]
        need_total = jnp.sum(need.astype(jnp.int32))
        return DevicePagePlan(exhausted=need_total > n_free, n_free=n_free,
                              need_by_group=need_by_group, rows=r, blocks=jb,
                              need=need, copy=copy, cur=cur, new=new)
    # segment-local allocation: rank each needing lane WITHIN its row's
    # shard (cumsum over a lane × shard one-hot — L and n_shards are both
    # small) and pop from that shard's stack only
    lane_sh = row_shard[r]
    onehot = ((lane_sh[:, None]
               == jnp.arange(n_shards, dtype=jnp.int32)[None, :])
              & need[:, None]).astype(jnp.int32)          # (L, n_shards)
    ni = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                             lane_sh[:, None], axis=1)[:, 0]
    new = stack_sh[lane_sh, jnp.clip(jnp.where(need, ni, 0), 0, pps - 1)]
    need_by_shard = jnp.sum(onehot, axis=0)
    exhausted_by_shard = need_by_shard > n_free_sh
    return DevicePagePlan(exhausted=jnp.any(exhausted_by_shard),
                          n_free=n_free, need_by_group=need_by_group,
                          rows=r, blocks=jb, need=need, copy=copy, cur=cur,
                          new=new, need_by_shard=need_by_shard,
                          n_free_by_shard=n_free_sh,
                          exhausted_by_shard=exhausted_by_shard)


def apply_page_plan(cache, plan: DevicePagePlan):
    """Apply a non-exhausted plan to every paged node of a model cache:
    scatter the new table entries, copy the draft-boundary pages
    (committed prefix rides along; stale draft slots past ``pos`` are
    overwritten pre-read by the next step), and mark fresh pages empty
    (stored position -1). The caller predicates on ``plan.exhausted`` —
    an exhausted iteration must apply nothing (preempt-and-replay)."""
    leaves, treedef, idx = paged_cache_entries(cache)
    P = int(leaves[idx[0]].pos.shape[1])
    n_rows = int(leaves[idx[0]].block_tables.shape[1])
    rr = jnp.where(plan.need, plan.rows, n_rows)
    copy_dst = jnp.where(plan.copy, plan.new, P)
    copy_src = jnp.clip(jnp.where(plan.copy, plan.cur, 0), 0, P - 1)
    fresh_dst = jnp.where(plan.need & ~plan.copy, plan.new, P)
    bt_new = leaves[idx[0]].block_tables[0].at[
        rr, plan.blocks].set(plan.new, mode="drop")
    def copy_pages(pool):
        # one gather per stacked layer: the single all-layer gather of a
        # page-sharded pool is refused by the TPU compiler under SPMD
        # (scoped VMEM overflow in its gather fusion)
        for layer in range(pool.shape[0]):
            pool = pool.at[layer, copy_dst].set(pool[layer, copy_src],
                                                mode="drop")
        return pool

    for i in idx:
        sc = leaves[i]
        k_pool = copy_pages(sc.k_pool)
        v_pool = copy_pages(sc.v_pool)
        pos = sc.pos.at[:, copy_dst].set(sc.pos[:, copy_src], mode="drop")
        pos = pos.at[:, fresh_dst].set(-1, mode="drop")
        leaves[i] = dataclasses.replace(
            sc, k_pool=k_pool, v_pool=v_pool, pos=pos,
            block_tables=jnp.broadcast_to(
                bt_new[None], sc.block_tables.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _is_stop_token(spec: SessionSpec, tok: jnp.ndarray,
                   stop_ids: jnp.ndarray) -> jnp.ndarray:
    """True where ``tok`` terminates its slot's sequence: the session-wide
    EOS, or one of the slot's per-request ``stop_ids``. ``tok`` is
    (S, ...); ``stop_ids`` is (S, n_stop) with -1 = unused (token ids are
    non-negative, so -1 never matches). n_stop == 0 reduces exactly to the
    EOS-only check."""
    hit = tok == spec.eos_id
    if spec.n_stop:
        extra = jnp.expand_dims(tok, -1) == jnp.expand_dims(
            stop_ids, tuple(range(1, tok.ndim)))
        hit = hit | jnp.any(extra, axis=-1)
    return hit


def _accept_lengths(greedy_tok: jnp.ndarray, drafts: jnp.ndarray,
                    draft_mask: jnp.ndarray) -> jnp.ndarray:
    """greedy_tok: (..., N_d, DL+1) argmax predictions; drafts:
    (..., N_d, DL). Returns (..., N_d): longest prefix where draft token i
    equals the model's argmax prediction for that position."""
    if drafts.shape[-1] == 0:
        return jnp.zeros(drafts.shape[:-1], jnp.int32)
    match = (drafts == greedy_tok[..., :-1]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(match, axis=-1), axis=-1)
    return jnp.where(draft_mask, n_acc, 0)


def _forward(spec: SessionSpec, handle: DecoderHandle, state: SessionState):
    """One verify pass over all slots × beams × drafts (the paper's
    effective-batch inflation, applied session-wide). Inactive slots feed
    position -1 so their cache writes land in the trash slot/page — a
    freed (or mid-prefill, see ``serving.backend``) slot's rows are never
    clobbered by the shared step."""
    S, K, N_d, DL = (spec.n_slots, spec.n_beams, spec.n_drafts,
                     spec.draft_len)
    rel = jnp.arange(DL + 1, dtype=jnp.int32)
    last_e = jnp.repeat(state.last.reshape(S * K), N_d)
    drafts_rows = jnp.broadcast_to(
        state.drafts[:, None], (S, K, N_d, DL)).reshape(S * K * N_d, DL)
    toks = jnp.concatenate([last_e[:, None], drafts_rows], axis=1)
    pos_e = jnp.repeat(state.pos.reshape(S * K), N_d)[:, None] + rel[None, :]
    active_e = jnp.repeat(state.active, K * N_d)
    pos_e = jnp.where(active_e[:, None], pos_e, -1)
    logits, cache = handle.decode_step(state.cache, toks, pos_e)
    return logits, cache, drafts_rows, rel


def _greedy_family_step(spec: SessionSpec, handle: DecoderHandle,
                        state: SessionState) -> SessionState:
    """Speculative greedy (and with DL=0, plain greedy): accept the longest
    argmax-matching draft prefix + one bonus token per slot. K == 1."""
    S, N_d, DL = spec.n_slots, spec.n_drafts, spec.draft_len
    max_new, pad_id = spec.max_new, spec.pad_id
    logits, cache, _, rel = _forward(spec, handle, state)

    finished = state.finished[:, 0] | ~state.active
    last, pos = state.last[:, 0], state.pos[:, 0]
    n_out, out = state.n_out[:, 0], state.tokens[:, 0]
    max_out = state.max_out                                      # (S,)

    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_tok = greedy_tok.reshape(S, N_d, DL + 1)

    # --- accept / select best draft --------------------------------------
    # per-request draft windows: clamping the accept length to the slot's
    # eff_dl BEFORE best-draft selection makes a padded (N_d, DL) draft
    # matrix behave exactly like a DL'=eff_dl session (causal logits at
    # positions <= eff_dl are unaffected by the extra fed draft tokens)
    n_acc = _accept_lengths(greedy_tok, state.drafts, state.draft_mask)
    n_acc = jnp.minimum(n_acc, state.eff_dl[:, None])
    best = jnp.argmax(n_acc, axis=-1).astype(jnp.int32)          # (S,)
    # inactive slots must not MOVE rows either (their writes already land
    # in the trash slot/page): a garbage best != 0 would make sync_winner
    # clobber row 0 of a mid-prefill slot with a sibling's garbage row
    best = jnp.where(state.active, best, 0)
    n_acc_b = jnp.take_along_axis(n_acc, best[:, None], axis=1)[:, 0]
    new_toks = jnp.take_along_axis(
        greedy_tok, best[:, None, None], axis=1)[:, 0]           # (S, DL+1)

    # --- EOS/stop + budget truncation -------------------------------------
    within = rel[None, :] <= n_acc_b[:, None]
    is_eos = _is_stop_token(spec, new_toks, state.stop_ids) & within
    any_eos = jnp.any(is_eos, axis=1)
    first_eos = jnp.argmax(is_eos, axis=1)
    n_prop = jnp.where(any_eos, first_eos + 1, n_acc_b + 1)
    budget = max_out - n_out
    n_app = jnp.minimum(n_prop, budget)
    n_app = jnp.where(finished, 0, n_app)
    hit_eos = any_eos & (first_eos + 1 <= budget) & ~finished

    # --- write accepted tokens --------------------------------------------
    write = rel[None, :] < n_app[:, None]
    idx = n_out[:, None] + rel[None, :]
    idx = jnp.where(write, idx, max_new)                         # drop invalid
    b_idx = jnp.arange(S)[:, None]
    out = out.at[b_idx, idx].set(new_toks, mode="drop")

    # --- commit: recurrent-state checkpoint + winner cache sync -----------
    cache = handle.commit_cache(cache, jnp.repeat(n_app, N_d))
    cache = sync_winner(cache, best, N_d)

    last_idx = jnp.clip(n_app - 1, 0, DL)
    new_last = jnp.take_along_axis(new_toks, last_idx[:, None], axis=1)[:, 0]
    last = jnp.where(n_app > 0, new_last, last)
    pos = pos + n_app
    n_out = n_out + n_app
    new_finished = finished | hit_eos | (n_out >= max_out)
    acc_used = jnp.minimum(n_acc_b, n_app)
    return state._replace(
        tokens=out[:, None], last=last[:, None], pos=pos[:, None],
        n_out=n_out[:, None], finished=new_finished[:, None], cache=cache,
        n_calls=state.n_calls + state.active.astype(jnp.int32),
        accepted=state.accepted + acc_used)


def _beam_family_step(spec: SessionSpec, handle: DecoderHandle,
                      state: SessionState) -> SessionState:
    """Speculative beam search, batched over S slots (and with DL=0, plain
    beam search — the paper's "SBS, DL=0" control). Per slot: candidates
    of unequal lengths beam ++ draft[:a] ++ w, global top-K (Alg. 1)."""
    S, K, N_d, DL = (spec.n_slots, spec.n_beams, spec.n_drafts,
                     spec.draft_len)
    A = DL + 1
    max_new, pad_id = spec.max_new, spec.pad_id
    V = handle.vocab_size
    logits, cache, drafts_rows, rel = _forward(spec, handle, state)

    fin = state.finished | ~state.active[:, None]                # (S, K)
    max_out = state.max_out                                      # (S,)

    lp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lp_all = lp_all.at[:, :, pad_id].set(_NEG)   # pad is never a real emission
    lp_all = lp_all.reshape(S, K, N_d, A, V)
    greedy_tok = jnp.argmax(lp_all, axis=-1).astype(jnp.int32)

    # ---- best draft per beam ---------------------------------------------
    d4 = drafts_rows.reshape(S, K, N_d, DL)
    dm = jnp.broadcast_to(state.draft_mask[:, None], (S, K, N_d))
    n_acc = _accept_lengths(greedy_tok, d4, dm)                  # (S, K, N_d)
    # per-request draft window (see the greedy-family step): clamp BEFORE
    # best-draft selection so padded drafts act like eff_dl-length ones
    n_acc = jnp.minimum(n_acc, state.eff_dl[:, None, None])
    best = jnp.argmax(n_acc, axis=-1).astype(jnp.int32)          # (S, K)
    # inactive slots must not MOVE rows (mid-prefill row-0 protection,
    # same as the greedy family): identity winner ...
    best = jnp.where(state.active[:, None], best, 0)

    def take_best(x):
        idx = best.reshape(S, K, 1, *([1] * (x.ndim - 3)))
        return jnp.take_along_axis(x, idx, axis=2)[:, :, 0]

    lp_best = take_best(lp_all)                                  # (S, K, A, V)
    draft_best = take_best(d4)                                   # (S, K, DL)
    n_acc_b = jnp.take_along_axis(n_acc, best[..., None], axis=2)[..., 0]

    # ---- candidates of unequal lengths -----------------------------------
    # cum[a] = sum of draft-token logps for prefix length a
    d_lp = jnp.take_along_axis(
        lp_best[:, :, :DL, :], draft_best[..., None], axis=3)[..., 0]
    cum = jnp.concatenate(
        [jnp.zeros((S, K, 1), jnp.float32), jnp.cumsum(d_lp, axis=-1)],
        axis=-1)                                                 # (S, K, A)
    topv, topi = jax.lax.top_k(lp_best, K)                       # (S, K, A, K)
    cand_lp = state.logp[:, :, None, None] + cum[..., None] + topv
    valid_a = rel[None, None, :] <= n_acc_b[..., None]           # (S, K, A)
    # budget: a+1 tokens must fit the slot's remaining per-request budget
    valid_a &= ((state.n_out[..., None] + rel[None, None, :] + 1)
                <= max_out[:, None, None])
    # prefixes may not extend past a draft EOS/stop token
    draft_eos = jnp.cumsum(
        _is_stop_token(spec, draft_best, state.stop_ids).astype(jnp.int32),
        axis=-1)
    no_eos_in_prefix = jnp.concatenate(
        [jnp.ones((S, K, 1), jnp.int32), (draft_eos == 0).astype(jnp.int32)],
        axis=-1)
    valid_a &= no_eos_in_prefix.astype(bool)
    cand_lp = jnp.where(valid_a[..., None], cand_lp, _NEG)
    # per-request beam width: an eff_beams < K request only ever extends
    # with the top-eff_beams tokens per (parent, prefix) — the candidate
    # multiset of a true eff_beams-wide search (ranks >= eff_beams at _NEG)
    k_rank = jnp.arange(K, dtype=jnp.int32)
    cand_lp = jnp.where(
        k_rank[None, None, None, :] < state.eff_beams[:, None, None, None],
        cand_lp, _NEG)

    # Same-path dedup: (a, w=draft[a]) with a < n_acc is a strict prefix of a
    # longer candidate in this set; keeping it would crowd out genuine
    # alternatives (only frontier candidates, as in the paper's Fig. 3).
    d_pad = jnp.pad(draft_best, ((0, 0), (0, 0), (0, 1)), constant_values=-1)
    dup = ((topi == d_pad[..., None])
           & (rel[None, None, :, None] < n_acc_b[..., None, None]))
    cand_lp = jnp.where(dup, _NEG, cand_lp)

    # finished beams: single pass-through candidate (a=0, k=0), logp kept
    pass_lp = jnp.full((A, K), _NEG).at[0, 0].set(0.0)
    cand_lp = jnp.where(fin[..., None, None],
                        state.logp[:, :, None, None] + pass_lp[None, None],
                        cand_lp)

    # ---- per-slot global top-K -------------------------------------------
    flat = cand_lp.reshape(S, K * A * K)
    new_logp, flat_idx = jax.lax.top_k(flat, K)                  # (S, K)
    parent = (flat_idx // (A * K)).astype(jnp.int32)
    # ... and identity parents, so the beam gather below can never pull a
    # garbage sibling row over a mid-prefill slot's row 0
    parent = jnp.where(state.active[:, None], parent, k_rank[None, :])
    a_len = ((flat_idx // K) % A).astype(jnp.int32)
    w_tok = jnp.take_along_axis(topi.reshape(S, K * A * K), flat_idx, axis=1)
    was_fin = jnp.take_along_axis(fin, parent, axis=1)

    def take_parent(x):
        idx = parent.reshape(S, K, *([1] * (x.ndim - 2)))
        return jnp.take_along_axis(x, idx, axis=1)

    # ---- materialize new beams (fixed-shape writes) ----------------------
    out_p = take_parent(state.tokens)                            # (S,K,max_new)
    nout_p = jnp.take_along_axis(state.n_out, parent, axis=1)
    drafts_p = take_parent(draft_best)                           # (S, K, DL)
    # committed tokens this round: draft[:a] ++ w  -> length a+1
    seg = jnp.where(rel[None, None, :] < a_len[..., None],
                    jnp.pad(drafts_p, ((0, 0), (0, 0), (0, 1))),
                    jnp.where(rel[None, None, :] == a_len[..., None],
                              w_tok[..., None], pad_id))
    n_new = jnp.where(was_fin, 0, a_len + 1)
    idx = nout_p[..., None] + rel[None, None, :]
    idx = jnp.where(rel[None, None, :] < n_new[..., None], idx, max_new)
    s_ix = jnp.arange(S)[:, None, None]
    k_ix = jnp.arange(K)[None, :, None]
    out_new = out_p.at[s_ix, k_ix, idx].set(seg, mode="drop")

    new_finished = (was_fin | _is_stop_token(spec, w_tok, state.stop_ids)
                    | (nout_p + n_new >= max_out[:, None]))
    # beams past the slot's eff_beams are parked: _NEG log-prob + finished,
    # so they never spawn candidates and sort last at read-out — the slot
    # behaves as a true eff_beams-wide search (no-op when eff_beams == K)
    parked = k_rank[None, :] >= state.eff_beams[:, None]
    new_logp = jnp.where(parked, _NEG, new_logp)
    new_finished = new_finished | parked
    new_last = jnp.where(was_fin,
                         jnp.take_along_axis(state.last, parent, axis=1),
                         w_tok)
    new_pos = jnp.take_along_axis(state.pos, parent, axis=1) + n_new
    new_nout = nout_p + n_new

    # ---- cache: winner-draft row of the parent beam, then commit the
    # candidate's own prefix length (recurrent-state rollback) -------------
    best_p = jnp.take_along_axis(best, parent, axis=1)           # (S, K)
    base = (jnp.arange(S, dtype=jnp.int32) * K)[:, None]
    src = ((base + parent) * N_d + best_p).reshape(-1)
    cache = gather_rows(cache, jnp.repeat(src, N_d))
    n_keep = jnp.where(was_fin, 0, a_len + 1)
    cache = handle.commit_cache(cache, jnp.repeat(n_keep.reshape(-1), N_d))

    acc = jnp.where(state.active & ~was_fin[:, 0], a_len[:, 0], 0)
    return state._replace(
        tokens=out_new, logp=new_logp, last=new_last, pos=new_pos,
        n_out=new_nout, finished=new_finished, cache=cache,
        n_calls=state.n_calls + state.active.astype(jnp.int32),
        accepted=state.accepted + acc)


def session_step(spec: SessionSpec, handle: DecoderHandle,
                 state: SessionState) -> SessionState:
    """ONE decode iteration for every slot: verify forward pass -> accept ->
    commit. Pure and shape-stable — jit it once per SessionSpec."""
    if spec.kind == "greedy":
        if spec.n_beams != 1:
            raise ValueError("greedy-family sessions require n_beams == 1")
        return _greedy_family_step(spec, handle, state)
    if spec.kind == "beam":
        return _beam_family_step(spec, handle, state)
    raise ValueError(f"unknown session kind: {spec.kind!r}")


def run_session(spec: SessionSpec, handle: DecoderHandle,
                state: SessionState):
    """Drain all resident requests (no admissions): while_loop over the
    shared step. Returns (state, n_iterations). Used by the one-shot decode
    wrappers; the continuous scheduler instead steps from the host."""

    def cond(carry):
        st, i = carry
        done = st.finished | ~st.active[:, None]
        return (i < spec.max_new) & ~jnp.all(done)

    def body(carry):
        st, i = carry
        return session_step(spec, handle, st), i + 1

    return jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
