"""Cross-request prefix page sharing + tree-of-requests serving.

The contract that makes search-tree traffic (retrosynthetic planning)
safe to serve from shared pages:

  1. sharing is INVISIBLE in the tokens: a child request admitted by
     aliasing its parent's committed prefix pages produces byte-identical
     output to submitting the full prompt cold — greedy and speculative,
     paged and dense, both backends (seq2seq reuses encoder outputs, a
     dense decoder cache is a silent no-op);
  2. the tree-of-requests API composes with the front door: children
     inherit mode/priority, pruning a subtree cancels every descendant
     AND returns the subtree's cached pages to the pool;
  3. retained prefix pages are a cache, not a leak: under pool pressure
     the radix tree reclaims before residents are preempted, and a full
     clear leaves every pool page free;
  4. the device page plan treats index-cell references like any other:
     shared pages are never elected copy-on-write keepers by a
     non-owner, so a writer always copies first (edge cases pinned
     below, straight on ``device_page_plan``);
  5. allocator invariants survive ANY interleaving of submit_child /
     cancel / drain (property-based, seeded in CI).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic env: in-repo fallback (see pyproject [dev])
    from repro.testing import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.mt import tiny_config
from repro.core import SessionSpec
from repro.core.session import (GroupedState, apply_page_plan,
                                device_free_pages, device_page_plan,
                                init_state, radix_cell_coords)
from repro.data import SyntheticReactionDataset
from repro.models import seq2seq as s2s
from repro.models import transformer as tr
from repro.models.attention import PagedKVCache
from repro.serving import EngineConfig, StreamingEngine
from repro.serving.api import RequestCancelled

MAX_NEW = 10
EOS = 2
DL, ND = 4, 5
PS, CHUNK = 8, 8   # page_size == prefill_chunk -> every full page shareable


@pytest.fixture(scope="module")
def decoder_model():
    cfg = get_config("smollm-135m", reduced=True)
    params = tr.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def toy_mt():
    ds = SyntheticReactionDataset(16, seed=0)
    cfg = tiny_config(ds.tokenizer.vocab_size, depth=2, d_model=64,
                      max_len=192)
    params = s2s.init(jax.random.PRNGKey(0), cfg)
    return ds, cfg, params


def _dec_engine(decoder_model, mode, *, share, paged=True, **kw):
    cfg, params = decoder_model
    base = dict(mode=mode, draft_len=DL, n_drafts=ND, max_new=MAX_NEW,
                max_src=96, n_slots=2, prefill_chunk=CHUNK, eos_id=EOS,
                prefix_cache=share)
    if paged:
        base.update(paged=True, page_size=PS)
    base.update(kw)
    return StreamingEngine(params, cfg, None, EngineConfig(**base))


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    root = rng.integers(4, 500, size=25).astype(np.int32)
    suffixes = [rng.integers(4, 500, size=n).astype(np.int32)
                for n in (8, 13, 8, 21)]
    return root, suffixes


def _serve_tree(eng):
    """Root -> two children -> two grandchildren of child 0, each parent
    finished (pages committed) before its children are admitted. Returns
    token arrays in submission order."""
    root, sfx = _prompts()
    h = eng.submit(root)
    out = [np.asarray(h.result().tokens[0])]
    kids = [h.submit_child(sfx[0]), h.submit_child(sfx[1])]
    out.append(np.asarray(kids[0].result().tokens[0]))
    out.append(np.asarray(kids[1].result().tokens[0]))
    grand = [kids[0].submit_child(sfx[2]), kids[0].submit_child(sfx[3])]
    out.extend(np.asarray(g.result().tokens[0]) for g in grand)
    return out


# ---------------------------------------------------------------------------
# 1. sharing is token-invisible: shared tree == cold full prompts


@pytest.mark.parametrize("mode", ["greedy", "speculative"])
@pytest.mark.parametrize("paged", [True, False])
def test_decoder_tree_identity(decoder_model, mode, paged):
    """submit_child served from aliased prefix pages (paged) — or with
    sharing silently disabled (dense) — must emit byte-identical tokens
    to a cold engine fed the fully concatenated prompts."""
    shared = _dec_engine(decoder_model, mode, share=True, paged=paged)
    cold = _dec_engine(decoder_model, mode, share=False, paged=paged)
    got = _serve_tree(shared)
    want = _serve_tree(cold)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    if paged:
        stats = shared.prefix_stats()
        assert stats["prefix_hit_rate"] > 0.0, stats
        # children re-prefill only their suffixes: strictly fewer pages
        # than the cold engine pays for the same tree
        assert (stats["pages_per_request"]
                < cold.prefix_stats()["pages_per_request"]), stats
        shared.allocator.check()
        shared.radix.check()
    else:
        # dense decoder cache: nothing to alias, prefix_cache is a no-op
        assert shared.radix is None


@pytest.mark.parametrize("mode", ["greedy", "speculative"])
@pytest.mark.parametrize("paged", [True, False])
def test_seq2seq_encode_reuse_identity(toy_mt, mode, paged):
    """The seq2seq analog of prefix sharing is the encoder-output LRU:
    repeated sources skip the encoder but must stay byte-identical, hit
    or miss, dense or paged."""
    ds, cfg, params = toy_mt
    kw = dict(mode=mode, max_new=MAX_NEW, max_src=96, n_slots=2)
    if mode == "speculative":
        kw.update(draft_len=4, n_drafts=6)
    if paged:
        kw.update(paged=True, page_size=8)
    shared = StreamingEngine(params, cfg, ds.tokenizer,
                             EngineConfig(prefix_cache=True, **kw))
    cold = StreamingEngine(params, cfg, ds.tokenizer, EngineConfig(**kw))
    # repeats interleaved with strangers: hits admitted next to misses
    queries = [ds.pair(i)[0] for i in (0, 1, 0, 2, 1, 0)]
    a = shared.predict(queries)
    b = cold.predict(queries)
    assert [p.smiles[0] for p in a] == [p.smiles[0] for p in b]
    stats = shared.prefix_stats()
    assert stats["lookups"] == len(queries)
    assert stats["hit_tokens"] > 0, stats
    assert cold.prefix_stats()["hit_tokens"] == 0


# ---------------------------------------------------------------------------
# 2. tree-of-requests API: inheritance, pruning, page reclamation


def test_submit_child_inherits_and_validates(decoder_model):
    eng = _dec_engine(decoder_model, "greedy", share=True)
    root, sfx = _prompts()
    h = eng.submit(root, priority=3)
    h.result()
    child = h.submit_child(sfx[0])
    assert child.mode == h.mode
    rec = eng._lineage[int(child)]
    assert rec["parent"] == int(h) and rec["priority"] == 3
    assert int(child) in eng._lineage[int(h)]["children"]
    child.result()
    with pytest.raises(KeyError):
        eng.submit_child(10 ** 9, sfx[0])


def test_cancel_subtree_releases_cached_pages(decoder_model):
    """Pruning a search subtree cancels every descendant and drops the
    subtree's radix nodes; a full clear then leaves the pool entirely
    free — retention is a cache, never a leak."""
    eng = _dec_engine(decoder_model, "greedy", share=True)
    root, sfx = _prompts()
    h = eng.submit(root)
    h.result()
    kids = [h.submit_child(s) for s in sfx[:2]]
    for k in kids:
        k.result()
    grand = kids[0].submit_child(sfx[2])
    nodes_before = len(eng.radix)
    assert nodes_before > 0
    assert h.cancel(recursive=True)
    assert grand.status == "cancelled"
    with pytest.raises(RequestCancelled):
        grand.result()
    # finished requests stay terminal ("done"), but their cached page
    # subtree is gone
    assert len(eng.radix) < nodes_before
    eng.radix.check()
    eng.clear_prefix_cache()
    assert len(eng.radix) == 0
    n_pages, _ = eng._paged_geometry()
    free = int(device_free_pages(eng.scheduler.state.cache, n_pages))
    assert free == n_pages - 1, (free, n_pages)   # all but the trash page
    eng.allocator.check()


def test_radix_reclaim_under_pool_pressure(decoder_model):
    """A pool too small to retain every tree's pages: the scheduler
    reclaims LRU radix nodes instead of preempting residents, and every
    request still completes."""
    eng = _dec_engine(decoder_model, "greedy", share=True, n_slots=2,
                      n_pages=14, max_src=64, prefix_cache_pages=8)
    rng = np.random.default_rng(7)
    handles = []
    for _ in range(6):
        p = rng.integers(4, 500, size=41).astype(np.int32)
        handles.append(eng.submit(p))
    for h in handles:
        assert h.result().status == "finished"
    assert eng.radix.evicted > 0, "pool was sized to force radix reclaim"
    eng.allocator.check()
    eng.radix.check()


def test_stream_late_attach(decoder_model):
    """A stream opened after iterations already committed tokens catches
    up with ONE backfill read and then yields deltas whose concatenation
    equals the final token array exactly."""
    eng = _dec_engine(decoder_model, "greedy", share=True)
    root, _ = _prompts()
    h = eng.submit(root)
    pump = eng.serve_steps()
    for _ in zip(range(6), pump):  # commit a few tokens before attaching
        pass
    deltas = list(h.stream())
    got = np.concatenate([d for d in deltas if d.size] or
                         [np.zeros(0, np.int32)])
    r = eng.wait(h.rid)
    np.testing.assert_array_equal(got, np.asarray(r.tokens[0])[:r.lengths[0]])


# ---------------------------------------------------------------------------
# 3. device_page_plan edge cases: index-cell refs drive CoW election


def _plan_fixture(n_pages=12, table=None, pos=0, active=True):
    """One greedy group (2 slots, 1 row each) + 1 index row over a tiny
    pool. Returns (specs, blocks, gstate) for direct device_page_plan
    calls; ``table`` rows are (group rows..., index row)."""
    spec = SessionSpec(n_slots=2, n_beams=1, n_drafts=1, draft_len=4,
                       max_new=8, eos_id=EOS)
    ps = 4
    n_blocks = -(-spec.cache_len // ps)
    bt = np.full((spec.n_rows + 1, n_blocks), -1, np.int32)
    if table is not None:
        for r, row in enumerate(table):
            bt[r, :len(row)] = row
    # session-level paged nodes stack layers on a leading axis (1 here)
    cache = PagedKVCache(
        k_pool=jnp.zeros((1, n_pages, ps, 4)),
        v_pool=jnp.zeros((1, n_pages, ps, 4)),
        pos=jnp.full((1, n_pages, ps), -1, jnp.int32),
        block_tables=jnp.asarray(bt)[None])
    state = init_state(spec, None)
    state = state._replace(
        active=state.active.at[0].set(bool(active)),
        pos=state.pos.at[0, 0].set(int(pos)),
        finished=state.finished.at[0].set(not active))
    gstate = GroupedState(groups=(state,), cache=cache)
    return (spec,), (n_blocks,), ps, gstate


def test_page_plan_zero_resident_slots():
    """No resident slots: the plan needs nothing, never exhausts, and
    counts the whole pool (minus trash) free."""
    specs, blocks, ps, gstate = _plan_fixture(active=False)
    plan = device_page_plan(specs, blocks, ps, 12, gstate)
    assert int(plan.need.sum()) == 0
    assert not bool(plan.exhausted)
    assert int(plan.n_free) == 11


def test_page_plan_fully_free_pool_allocates_ascending():
    """First touch of an empty pool: the write window's unmapped blocks
    draw fresh pages off the ascending free stack (page 0 = trash is
    never handed out)."""
    specs, blocks, ps, gstate = _plan_fixture(pos=0)
    plan = device_page_plan(specs, blocks, ps, 12, gstate)
    got = sorted(np.asarray(plan.new)[np.asarray(plan.need)].tolist())
    assert got == [1, 2]          # blocks 0..(0+DL)//ps, lowest ids first
    assert not bool(plan.exhausted)
    cache = apply_page_plan(gstate.cache, plan)
    row = np.asarray(cache.block_tables[0, 0])
    assert row[0] == 1 and row[1] == 2


def test_page_plan_all_pages_referenced_exhausts():
    """Every pool page referenced somewhere: a sole-owner page inside the
    write window is still KEPT (refs == win_refs, highest-row keeper),
    while the unmapped frontier block finds the free stack empty and the
    plan raises the exhausted flag — all-or-nothing, applies zero."""
    # pages 1..5: row 0 holds page 3 in block 0; rows 1 + index row pin
    # the rest, so n_free == 0
    specs, blocks, ps, gstate = _plan_fixture(
        n_pages=6, pos=2,
        table=[[3], [1, 2], [4, 5]])
    plan = device_page_plan(specs, blocks, ps, 6, gstate)
    assert int(plan.n_free) == 0
    lanes = np.asarray(plan.need)
    keep_page = (np.asarray(plan.cur) == 3)
    assert not lanes[keep_page].any(), \
        "sole-owner page must be kept, not reallocated"
    assert bool(plan.exhausted)


def test_page_plan_shared_page_never_kept_by_non_owner():
    """A write-window page also referenced by a radix index cell (or any
    other row) must NOT be elected its CoW keeper: the lane reallocates
    and copies, leaving the shared page read-only."""
    # row 0's block 0 = page 3; the index row ALSO references page 3
    specs, blocks, ps, gstate = _plan_fixture(
        pos=2, table=[[3], [], [3]])
    plan = device_page_plan(specs, blocks, ps, 12, gstate)
    lanes = np.asarray(plan.need) & (np.asarray(plan.cur) == 3)
    assert lanes.any(), "shared page must be reallocated, not kept"
    assert np.asarray(plan.copy)[lanes].all(), \
        "mid-page boundary over a shared page must copy-on-write"
    assert (np.asarray(plan.new)[lanes] != 3).all()
    # the copy really duplicates the page: poison page 3 and apply
    cache = gstate.cache
    cache = cache.__class__(
        k_pool=cache.k_pool.at[:, 3].set(7.0), v_pool=cache.v_pool,
        pos=cache.pos.at[:, 3].set(2), block_tables=cache.block_tables)
    out = apply_page_plan(cache, plan)
    new_page = int(np.asarray(plan.new)[lanes][0])
    np.testing.assert_array_equal(np.asarray(out.k_pool[0, new_page]),
                                  np.asarray(cache.k_pool[0, 3]))
    assert int(np.asarray(out.block_tables)[0, 2, 0]) == 3, \
        "the index row keeps the original shared page"


def test_radix_cell_coords_span_index_rows():
    rows, blocks = radix_cell_coords(6, 4, range(10))
    assert rows.tolist() == [6, 6, 6, 6, 7, 7, 7, 7, 8, 8]
    assert blocks.tolist() == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]


# ---------------------------------------------------------------------------
# 4. property: allocator invariants under random tree interleavings

_HYP_ENGINE = []


def _hyp_engine():
    """One shared engine across examples (reset() between them) — the
    fallback property runner can't mix fixtures into @given tests."""
    if not _HYP_ENGINE:
        cfg = get_config("smollm-135m", reduced=True)
        params = tr.init(jax.random.PRNGKey(0), cfg)
        _HYP_ENGINE.append(StreamingEngine(params, cfg, None, EngineConfig(
            mode="greedy", max_new=6, max_src=96, n_slots=2,
            prefill_chunk=CHUNK, eos_id=EOS, paged=True, page_size=PS,
            prefix_cache=True)))
    return _HYP_ENGINE[0]


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(0, 10 ** 6), min_size=1, max_size=12))
def test_tree_ops_preserve_allocator_invariants(ops):
    """Any interleaving of submit / submit_child / drain / cancel
    (recursive or not) leaves refcounts consistent with live references,
    no page double-free, and — after pruning every tree and clearing the
    cache — zero leaked pages."""
    eng = _hyp_engine()
    eng.reset()
    rng = np.random.default_rng(ops[0])
    handles, roots = [], []
    for op in ops:
        kind = op % 4
        if kind == 1 and handles:       # expand a random known node
            parent = handles[(op // 4) % len(handles)]
            if len(eng._lineage[int(parent)]["query"]) < 70:
                handles.append(parent.submit_child(
                    rng.integers(4, 500, size=5 + op % 12)
                    .astype(np.int32)))
                continue
        if kind == 2 and handles:       # drain one request
            try:
                handles[(op // 4) % len(handles)].result()
            except RequestCancelled:
                pass
            continue
        if kind == 3 and handles:       # prune a random subtree
            handles[(op // 4) % len(handles)].cancel(
                recursive=bool((op // 4) % 2))
            continue
        h = eng.submit(rng.integers(4, 500, size=9 + op % 30)
                       .astype(np.int32))
        handles.append(h)
        roots.append(h)
    eng.serve()                         # drain everything still live
    rx = eng.radix
    rx.check()
    eng.allocator.check()
    assert all(nd.active == 0 for nd in rx._nodes_by_cell.values()), \
        "request refcounts must drop to zero once all requests terminate"
    for r in roots:
        r.cancel(recursive=True)
    eng.clear_prefix_cache()
    assert len(rx) == 0
    n_pages, _ = eng._paged_geometry()
    free = int(device_free_pages(eng.scheduler.state.cache, n_pages))
    assert free == n_pages - 1, f"leaked {n_pages - 1 - free} page(s)"
