"""DecodeSession + continuous-batching invariants.

The contract that makes continuous batching safe to ship:

  1. the StreamingEngine (fixed slots, queued admissions, shared jitted
     step) produces token-identical outputs to the per-request
     ReactionEngine for all four decoding modes;
  2. a request admitted mid-stream — next to strangers, into a recycled
     slot — yields byte-identical output to running it alone;
  3. batched beam search == the B=1 beam loop run per query (the lifted
     restriction changes nothing but wall-clock);
  4. vectorized draft extraction == the per-row reference, including
     dilated windows (paper §3.1);
  5. the paged KV cache is invisible: paged and dense sessions emit
     token-identical outputs for all four modes, the page allocator never
     double-allocates or leaks, and pool exhaustion defers admission (or
     preempts) — it never crashes and never changes tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic env: in-repo fallback (see pyproject [dev])
    from repro.testing import given, settings, strategies as st

from repro.configs.mt import tiny_config
from repro.core import (SessionSpec, batch_drafts, batched_beam_search,
                        batched_speculative_beam_search, beam_search,
                        extract_drafts, seq2seq_handle,
                        speculative_beam_search)
from repro.data import SyntheticReactionDataset
from repro.models import seq2seq as s2s
from repro.serving import EngineConfig, ReactionEngine, StreamingEngine

MAX_NEW = 20


# ---------------------------------------------------------------------------
# small random model (decoder behaviour only, no training needed)


@pytest.fixture(scope="module")
def toy():
    ds = SyntheticReactionDataset(16, seed=0)
    cfg = tiny_config(ds.tokenizer.vocab_size, depth=2, d_model=64,
                      max_len=192)
    params = s2s.init(jax.random.PRNGKey(0), cfg)
    return ds, cfg, params


def _engines(toy, **kw):
    ds, cfg, params = toy
    ecfg = EngineConfig(max_new=MAX_NEW, max_src=96, **kw)
    return (ReactionEngine(params, cfg, ds.tokenizer, ecfg),
            StreamingEngine(params, cfg, ds.tokenizer, ecfg))


# ---------------------------------------------------------------------------
# 1. continuous engine == per-request engine, all four modes


@pytest.mark.parametrize("mode,kw", [
    ("greedy", {}),
    ("speculative", dict(draft_len=4, n_drafts=6)),
])
def test_streaming_matches_batch_engine_greedy_family(toy, mode, kw):
    ds, _, _ = toy
    queries = [ds.pair(i)[0] for i in range(5)]
    ref, stream = _engines(toy, mode=mode, n_slots=2, **kw)
    a = ref.predict(queries)
    b = stream.predict(queries)
    assert [p.smiles[0] for p in a] == [p.smiles[0] for p in b]


@pytest.mark.parametrize("mode,kw", [
    ("beam", dict(n_beams=3)),
    ("speculative_beam", dict(n_beams=3, draft_len=4, n_drafts=6)),
])
def test_streaming_matches_batch_engine_beam_family(toy, mode, kw):
    ds, _, _ = toy
    queries = [ds.pair(i)[0] for i in range(3)]
    ref, stream = _engines(toy, mode=mode, n_slots=2, **kw)
    for q in queries:
        a = ref.predict_topn(q)
        b = stream.predict_topn(q)
        assert a.smiles == b.smiles
        np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# 2. scheduler admission/eviction invariants


def test_mid_stream_admission_is_isolated(toy):
    """A request admitted into a recycled slot while strangers occupy the
    other slots produces byte-identical tokens to running it alone."""
    ds, _, _ = toy
    queries = [ds.pair(i)[0] for i in range(6)]
    probe = queries[-1]

    _, alone = _engines(toy, mode="speculative", draft_len=4, n_drafts=6,
                        n_slots=2)
    alone_rid = alone.submit(probe)
    alone_res = alone.serve()[alone_rid]

    _, stream = _engines(toy, mode="speculative", draft_len=4, n_drafts=6,
                         n_slots=2)
    # five strangers first, probe arrives mid-stream (closed loop: arrival
    # is a decode-step count), so it lands in an already-recycled slot
    for q in queries[:-1]:
        stream.submit(q)
    probe_rid = stream.submit(probe, arrival=7.0)
    res = stream.serve()
    np.testing.assert_array_equal(res[probe_rid].tokens, alone_res.tokens)
    assert res[probe_rid].n_calls <= alone_res.n_calls + 1
    assert len(res) == 6


def test_eviction_frees_slots_for_queue(toy):
    """More requests than slots: every request completes, slots recycle."""
    ds, _, _ = toy
    queries = [ds.pair(i % 8)[0] for i in range(7)]
    _, stream = _engines(toy, mode="greedy", n_slots=2)
    rids = [stream.submit(q) for q in queries]
    res = stream.serve()
    assert sorted(res) == sorted(rids)
    ref, _ = _engines(toy, mode="greedy", n_slots=2)
    want = [p.smiles[0] for p in ref.predict(queries)]
    got = [ds.tokenizer.decode(res[r].tokens[0]) for r in rids]
    assert got == want


# ---------------------------------------------------------------------------
# 2b. paged KV cache: token identity + allocator invariants


PAGED_MODES = [
    ("greedy", {}),
    ("speculative", dict(draft_len=4, n_drafts=6)),
    ("beam", dict(n_beams=3)),
    ("speculative_beam", dict(n_beams=3, draft_len=4, n_drafts=6)),
]


@pytest.mark.parametrize("mode,kw", PAGED_MODES)
def test_paged_matches_dense_all_modes(toy, mode, kw):
    """Acceptance criterion: the paged cache is a pure memory-layout change
    — token-identical outputs (and beam log-probs) in all four modes. The
    pool is one lane-dense stack, (layers, pages, page_size, n_kv *
    head_dim), that every step writes in place; the beam modes reorder
    rows over shared pages and copy-on-write pages each step."""
    ds, cfg, _ = toy
    queries = [ds.pair(i)[0] for i in range(4)]
    _, dense = _engines(toy, mode=mode, n_slots=2, **kw)
    _, paged = _engines(toy, mode=mode, n_slots=2, paged=True, page_size=8,
                        **kw)
    pool = paged.scheduler.state.cache["self"].k_pool
    assert pool.shape == (cfg.n_layers, paged._paged_geometry()[0], 8,
                          cfg.n_kv_heads * cfg.head_dim)
    if mode in ("greedy", "speculative"):
        a, b = dense.predict(queries), paged.predict(queries)
        assert [p.smiles[0] for p in a] == [p.smiles[0] for p in b]
    else:
        for q in queries[:2]:
            a, b = dense.predict_topn(q), paged.predict_topn(q)
            assert a.smiles == b.smiles
            np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=1e-5,
                                       atol=1e-5)
    paged.allocator.check()
    # short sequences must not have touched the worst case
    fp = paged.cache_footprint()
    assert fp["peak_bytes"] <= fp["capacity_bytes"]


def test_paged_pool_exhaustion_defers_never_crashes(toy):
    """Oversubscription: a pool holding ~1 slot's worst case serves a
    4-slot session — admission defers on pool pressure (preempting when a
    resident outgrows it) and every request still completes with tokens
    identical to the dense session."""
    ds, _, _ = toy
    queries = [ds.pair(i % 8)[0] for i in range(8)]
    kw = dict(mode="speculative", draft_len=4, n_drafts=6)
    _, dense = _engines(toy, n_slots=4, **kw)
    # worst case per slot = n_drafts * ceil(cache_len/ps) pages; give the
    # pool barely more than one slot's worth
    _, paged = _engines(toy, n_slots=4, paged=True, page_size=8,
                        n_pages=1 + 6 * 4 + 4, **kw)
    fp = paged.cache_footprint()
    assert paged.spec.n_slots > fp["contiguous_equiv_slots"], \
        "pool must be smaller than the contiguous-row layout would need"
    a = dense.predict(queries)
    b = paged.predict(queries)
    assert [p.smiles[0] for p in a] == [p.smiles[0] for p in b]
    paged.allocator.check()


# ---- allocator property tests: driven with the session's own ops ----------


def _paged_session(spec, page_size, n_pages):
    """Synthetic paged session (no model): enough structure for the
    allocator — (R=1)-stacked PagedKVCache + the SessionState fields."""
    from repro.configs.mt import tiny_config
    from repro.core.session import PageAllocator, init_state
    from repro.models.attention import init_paged_kv_cache
    cfg = tiny_config(32, depth=1, d_model=16)
    pc = init_paged_kv_cache(cfg, spec.n_rows, spec.cache_len,
                             n_pages=n_pages, page_size=page_size)
    pc = jax.tree_util.tree_map(lambda a: a[None], pc)
    state = init_state(spec, {"self": pc})
    return PageAllocator(spec, n_pages=n_pages, page_size=page_size), state


def _window_refs(alloc, state, spec):
    """(live-row window pages, their refcounts across ALL rows)."""
    bt = np.asarray(state.cache["self"].block_tables[0])
    pos = np.asarray(state.pos)
    active = np.asarray(state.active)
    refs = np.bincount(bt[bt >= 0].ravel(), minlength=alloc.n_pages)
    K, N_d = spec.n_beams, spec.n_drafts
    out = []
    for s in np.flatnonzero(active):
        for k in range(K):
            for d in range(N_d):
                r = (s * K + k) * N_d + d
                for j in alloc.window_blocks(int(pos[s, k])):
                    out.append((int(bt[r, j]), int(refs[bt[r, j]])
                                if bt[r, j] >= 0 else 0))
    return out


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_page_allocator_invariants(seed):
    """Against random admit/decode/sync/release traces: (a) no page is ever
    double-allocated (every live write-window page is mapped and privately
    owned), (b) pages never leak — releasing everything returns the whole
    pool, (c) exhaustion surfaces as PoolExhausted, never corruption."""
    from repro.core.session import (PoolExhausted, release_slot, reset_slot,
                                    unmap_slot_pages)
    from repro.core.tree_batch import gather_rows, sync_winner
    rng = np.random.default_rng(seed)
    K, N_d, DL = int(rng.integers(1, 3)), int(rng.integers(1, 4)), 3
    spec = SessionSpec(n_slots=3, n_beams=K, n_drafts=N_d, draft_len=DL,
                       max_new=12, eos_id=1, kind="beam" if K > 1 else "greedy")
    ps = int(rng.choice([2, 4, 8]))
    n_blocks = -(-spec.cache_len // ps)
    n_pages = 1 + spec.rows_per_slot * n_blocks + int(rng.integers(0, 12))
    alloc, state = _paged_session(spec, ps, n_pages)
    resident: set[int] = set()
    empty_drafts = jnp.zeros((N_d, DL), jnp.int32)
    dmask = jnp.ones((N_d,), bool)

    for _ in range(25):
        op = rng.choice(["admit", "step", "release"])
        if op == "admit" and len(resident) < spec.n_slots:
            slot = int(rng.choice(list(set(range(spec.n_slots)) - resident)))
            state = unmap_slot_pages(spec, state, jnp.int32(slot))
            state = reset_slot(spec, state, jnp.int32(slot), 2, 0,
                               empty_drafts, dmask)
            resident.add(slot)
        elif op == "step" and resident:
            try:
                state = alloc.prepare_step(state)
            except PoolExhausted:
                alloc.reclaim(state)
                alloc.check()
                continue
            alloc.check()
            # every live window page is mapped and owned by exactly one row
            for page, nref in _window_refs(alloc, state, spec):
                assert page >= 1, "write-window block left unmapped"
                assert nref == 1, "write-window page shared between rows"
            # emulate the step's cache movement: advance + alias tables the
            # way winner-sync / beam-gather do
            adv = rng.integers(0, DL + 2, size=(spec.n_slots, K))
            pos = np.minimum(np.asarray(state.pos) + adv, spec.max_new)
            state = state._replace(pos=jnp.asarray(pos, jnp.int32))
            cache = state.cache
            if N_d > 1:
                best = jnp.asarray(rng.integers(0, N_d, spec.n_slots * K))
                cache = sync_winner(cache, best, N_d)
            if K > 1:
                parent = rng.integers(0, K, (spec.n_slots, K))
                base = (np.arange(spec.n_slots) * K)[:, None]
                src = np.repeat((base + parent).reshape(-1), N_d) * N_d \
                    + np.tile(np.arange(N_d), spec.n_slots * K)
                cache = gather_rows(cache, jnp.asarray(src))
            state = state._replace(cache=cache)
        elif op == "release" and resident:
            slot = int(rng.choice(list(resident)))
            state = release_slot(state, jnp.int32(slot))
            state = unmap_slot_pages(spec, state, jnp.int32(slot))
            resident.discard(slot)
            alloc.reclaim(state)
            alloc.check()

    # release everything: the allocator must get every page back
    for slot in list(resident):
        state = release_slot(state, jnp.int32(slot))
        state = unmap_slot_pages(spec, state, jnp.int32(slot))
    alloc.reclaim(state)
    alloc.check()
    assert alloc.free_pages == n_pages - 1, "pages leaked after full release"


def test_page_allocator_rejects_impossible_pool():
    """A pool that cannot hold even one slot's worst case is a config
    error at construction time — not a runtime deadlock."""
    from repro.core.session import PageAllocator
    spec = SessionSpec(n_slots=2, n_beams=1, n_drafts=4, draft_len=4,
                       max_new=16, eos_id=1)
    with pytest.raises(ValueError):
        PageAllocator(spec, n_pages=4, page_size=4)


# ---------------------------------------------------------------------------
# 3. batched beam == per-query B=1 beam


def test_batched_beam_matches_single_query(toy):
    ds, cfg, params = toy
    tok = ds.tokenizer
    B, n = 3, 4
    rows = [tok.encode_padded(ds.pair(i)[0], 64, add_eos=True)
            for i in range(B)]
    src = jnp.asarray(np.stack(rows))
    memory, src_mask = s2s.encode(params, cfg, src)
    handle = seq2seq_handle(params, cfg, memory_mask=src_mask)
    cache = s2s.init_cache(cfg, B, MAX_NEW + 2, memory=memory, params=params)
    batched = batched_beam_search(handle, cache, tok.bos_id,
                                  jnp.zeros((B,), jnp.int32), n_beams=n,
                                  max_new=MAX_NEW, eos_id=tok.eos_id)
    for b in range(B):
        memory1, mask1 = s2s.encode(params, cfg, src[b:b + 1])
        handle1 = seq2seq_handle(params, cfg, memory_mask=mask1)
        cache1 = s2s.init_cache(cfg, 1, MAX_NEW + 2, memory=memory1,
                                params=params)
        single = beam_search(handle1, cache1, tok.bos_id, 0, n_beams=n,
                             max_new=MAX_NEW, eos_id=tok.eos_id)
        np.testing.assert_array_equal(np.asarray(batched.tokens[b]),
                                      np.asarray(single.tokens))
        np.testing.assert_allclose(np.asarray(batched.logprobs[b]),
                                   np.asarray(single.logprobs),
                                   rtol=1e-5, atol=1e-5)


def test_batched_sbs_matches_single_query(toy):
    ds, cfg, params = toy
    tok = ds.tokenizer
    B, n, DL, N_d = 2, 3, 4, 5
    rows = [tok.encode_padded(ds.pair(i)[0], 64, add_eos=True)
            for i in range(B)]
    src = jnp.asarray(np.stack(rows))
    dd, mm = zip(*(extract_drafts(r, DL, N_d) for r in np.stack(rows)))
    drafts, dmask = jnp.asarray(np.stack(dd)), jnp.asarray(np.stack(mm))
    memory, src_mask = s2s.encode(params, cfg, src)
    handle = seq2seq_handle(params, cfg, memory_mask=src_mask)
    cache = s2s.init_cache(cfg, B, MAX_NEW + DL + 2, memory=memory,
                           params=params)
    batched = batched_speculative_beam_search(
        handle, cache, tok.bos_id, jnp.zeros((B,), jnp.int32), drafts,
        dmask, n_beams=n, max_new=MAX_NEW, eos_id=tok.eos_id)
    for b in range(B):
        memory1, mask1 = s2s.encode(params, cfg, src[b:b + 1])
        handle1 = seq2seq_handle(params, cfg, memory_mask=mask1)
        cache1 = s2s.init_cache(cfg, 1, MAX_NEW + DL + 2, memory=memory1,
                                params=params)
        single = speculative_beam_search(
            handle1, cache1, tok.bos_id, 0, drafts[b], dmask[b], n_beams=n,
            max_new=MAX_NEW, eos_id=tok.eos_id)
        np.testing.assert_array_equal(np.asarray(batched.tokens[b]),
                                      np.asarray(single.tokens))


# ---------------------------------------------------------------------------
# 4. drafting: vectorized batch == per-row reference, incl. dilations


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 8), st.integers(1, 28))
def test_batch_drafts_matches_reference(seed, dl, nd):
    rng = np.random.default_rng(seed)
    B, T = int(rng.integers(1, 6)), int(rng.integers(0, 40))
    toks = rng.integers(0, 24, size=(B, T)).astype(np.int32)  # incl. pads
    for dilations in ((1,), (1, 2), (2,), (1, 2, 3)):
        got_d, got_m = batch_drafts(toks, dl, nd, dilations=dilations)
        ds_, ms_ = zip(*(extract_drafts(r, dl, nd, dilations=dilations)
                         for r in toks))
        np.testing.assert_array_equal(got_d, np.stack(ds_))
        np.testing.assert_array_equal(got_m, np.stack(ms_))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(4, 60), min_size=2, max_size=40),
       st.integers(2, 6))
def test_dilated_drafts_are_dilated_substrings(tokens, dl):
    """Property (paper §3.1): every masked dilation-2 draft is an
    every-other-token subsequence of the query."""
    drafts, mask = batch_drafts(np.asarray([tokens], np.int32), dl, 64,
                                dilations=(1, 2))
    toks = [t for t in tokens if t != 0]
    n1 = max(0, len(toks) - dl + 1) or (1 if toks else 0)  # stride-1 windows
    strided = {",".join(map(str, toks[s::2][:dl]))
               for s in range(len(toks))}
    for i in range(64):
        if not mask[0, i] or i < n1:
            continue
        w = [t for t in drafts[0, i] if t != 0]
        assert ",".join(map(str, w)) in strided
