"""Per-architecture smoke tests + decode-path consistency.

Decode consistency is the load-bearing property for the paper's technique:
``prefill + decode_step`` (the cached serving path, including multi-token
verification steps) must produce the same logits as the full-sequence
``apply``. Speculative decoding's accuracy-neutrality guarantee rests on it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import seq2seq as s2s
from repro.models import transformer as tr

DECODER_ARCHS = [
    "command-r-35b", "qwen3-8b", "llama-3.2-vision-11b", "jamba-v0.1-52b",
    "llama4-maverick-400b-a17b", "starcoder2-15b", "smollm-135m",
    "rwkv6-1.6b", "phi3.5-moe-42b-a6.6b",
]
ALL_ARCHS = DECODER_ARCHS + ["hubert-xlarge"]


def _inputs(cfg, key, B=2, T=16):
    kw = {}
    if cfg.family == "audio":
        kw["embeddings"] = jax.random.normal(key, (B, T, cfg.d_model)) * 0.1
        tokens = None
    else:
        tokens = jax.random.randint(key, (B, T), 4, cfg.vocab_size)
    if cfg.family == "vlm":
        kw["memory"] = jax.random.normal(key, (B, cfg.memory_tokens, cfg.memory_dim)) * 0.1
    return tokens, kw


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward(arch):
    """Reduced config: one forward pass, correct shapes, finite outputs."""
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(0)
    params = tr.init(key, cfg)
    tokens, kw = _inputs(cfg, key)
    logits, aux = tr.apply(params, cfg, tokens, **kw)
    B = 2
    T = 16
    assert logits.shape == (B, T, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    for v in aux.values():
        assert bool(jnp.isfinite(v))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    """One gradient step on the reduced config: finite loss and grads."""
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(1)
    params = tr.init(key, cfg)
    tokens, kw = _inputs(cfg, key, B=2, T=12)

    def loss_fn(p):
        logits, aux = tr.apply(p, cfg, tokens, **kw)
        if cfg.family == "audio":
            labels = jnp.zeros(logits.shape[:2], jnp.int32)
        else:
            labels = jnp.roll(tokens, -1, axis=1)
        ll = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(ll, labels[..., None], axis=-1))
        return loss + sum(aux.values(), jnp.float32(0))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_decode_matches_full(arch):
    """prefill + chunked decode_step logits == full-sequence apply logits."""
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(2)
    params = tr.init(key, cfg)
    B, T_pre, T_total = 2, 6, 12
    tokens, kw = _inputs(cfg, key, B=B, T=T_total)
    full_logits, _ = tr.apply(params, cfg, tokens, **kw)

    cache = tr.init_cache(cfg, B, max_len=32)
    memory = kw.get("memory")
    pre_logits, cache = tr.prefill(params, cfg, cache, tokens[:, :T_pre],
                                   memory=memory)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits[:, :T_pre]),
        rtol=2e-4, atol=2e-4)

    # decode the rest in chunks of 3 (multi-token steps, as verification does)
    pos0 = T_pre
    for start in range(T_pre, T_total, 3):
        chunk = tokens[:, start : start + 3]
        Tc = chunk.shape[1]
        positions = (jnp.arange(Tc) + start)[None, :].repeat(B, 0)
        step_logits, cache = tr.decode_step(params, cfg, cache, chunk, positions)
        cache = tr.commit_cache(cfg, cache, jnp.full((B,), Tc, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits[:, start : start + Tc]),
            rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["smollm-135m", "jamba-v0.1-52b", "rwkv6-1.6b"])
def test_prefill_ragged_lengths(arch):
    """Rows with different prompt lengths produce per-row-correct states:
    a short row inside a padded batch must match the same row run alone."""
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(3)
    params = tr.init(key, cfg)
    toks = jax.random.randint(key, (2, 10), 4, cfg.vocab_size)
    lengths = jnp.array([10, 6], jnp.int32)

    cache = tr.init_cache(cfg, 2, max_len=32)
    _, cache = tr.prefill(params, cfg, cache, toks, lengths=lengths)
    pos = jnp.array([[10], [6]], jnp.int32)
    nxt = jax.random.randint(jax.random.PRNGKey(4), (2, 1), 4, cfg.vocab_size)
    step_logits, _ = tr.decode_step(params, cfg, cache, nxt, pos)

    # row 1 alone, unpadded
    cache1 = tr.init_cache(cfg, 1, max_len=32)
    _, cache1 = tr.prefill(params, cfg, cache1, toks[1:2, :6])
    solo_logits, _ = tr.decode_step(params, cfg, cache1, nxt[1:2],
                                    jnp.array([[6]], jnp.int32))
    np.testing.assert_allclose(np.asarray(step_logits[1]), np.asarray(solo_logits[0]),
                               rtol=2e-4, atol=2e-4)


def test_seq2seq_decode_matches_full():
    """MT decoder: cached multi-token decode == teacher-forced decode."""
    from repro.configs.mt import tiny_config
    cfg = tiny_config(48, depth=2, d_model=64)
    key = jax.random.PRNGKey(5)
    params = s2s.init(key, cfg)
    B, S, T = 2, 14, 10
    src = jax.random.randint(key, (B, S), 4, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(6), (B, T), 4, cfg.vocab_size)
    memory, src_mask = s2s.encode(params, cfg, src)
    full = s2s.decode(params, cfg, tgt, memory, src_mask)

    cache = s2s.init_cache(cfg, B, max_len=32, memory=memory, params=params)
    for start in range(0, T, 4):
        chunk = tgt[:, start : start + 4]
        Tc = chunk.shape[1]
        positions = (jnp.arange(Tc) + start)[None, :].repeat(B, 0)
        logits, cache = s2s.decode_step(params, cfg, cache, chunk, positions,
                                        memory_mask=src_mask)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, start : start + Tc]),
                                   rtol=2e-4, atol=2e-4)


def _self_node(cache):
    """The self-attention cache node of a seq2seq dict or a decoder-only
    per-pattern-position tuple."""
    return cache["self"] if isinstance(cache, dict) else cache[0]


def _private_window_pages(cache, rows_blocks, next_page):
    """Copy-on-write as the megastep's page plan does it: map each (row,
    block) of the coming write window to a fresh page, copying the current
    page's contents (every layer) through ``apply_page_plan``. Returns the
    cache and the next unused page id."""
    from repro.core.session import DevicePagePlan, apply_page_plan
    bt = np.asarray(_self_node(cache).block_tables[0])
    rows = np.asarray([r for r, _ in rows_blocks], np.int32)
    blocks = np.asarray([b for _, b in rows_blocks], np.int32)
    cur = bt[rows, blocks]
    new = np.arange(next_page, next_page + len(rows), dtype=np.int32)
    plan = DevicePagePlan(
        exhausted=jnp.bool_(False), n_free=jnp.int32(0), need_by_group=None,
        rows=jnp.asarray(rows), blocks=jnp.asarray(blocks),
        need=jnp.ones(len(rows), bool), copy=jnp.asarray(cur >= 0),
        cur=jnp.asarray(cur), new=jnp.asarray(new))
    return apply_page_plan(cache, plan), next_page + len(rows)


def _paged_matches_dense(step, dense, paged, n_rows, steps, chunk, ps):
    """Feed ``steps`` chunks of ``chunk`` tokens through a dense and a paged
    cache. Between steps the rows are reordered as beam search does (the
    paged rows then alias pages) and every row's next write window gets
    private pages by copy-on-write. Each step's logits must match, and the
    layer-``l`` slice of the stacked pool must hold, heads folded, exactly
    the K/V the dense cache holds for layer ``l``."""
    from repro.core.tree_batch import gather_rows
    from repro.models.attention import PagedKVCache
    sc = _self_node(paged)
    assert isinstance(sc, PagedKVCache)
    R, n_blocks = sc.k_pool.shape[0], sc.block_tables.shape[-1]
    k_dense = _self_node(dense).k
    assert sc.k_pool.shape[2:] == (ps, k_dense.shape[-2] * k_dense.shape[-1])
    # map every block of every row to a distinct page up front
    bt = jnp.arange(1, n_rows * n_blocks + 1,
                    dtype=jnp.int32).reshape(n_rows, n_blocks)
    sc = dataclasses.replace(
        sc, block_tables=jnp.broadcast_to(bt, sc.block_tables.shape))
    paged = ({**paged, "self": sc} if isinstance(paged, dict)
             else (sc,) + tuple(paged[1:]))
    next_page = n_rows * n_blocks + 1
    rng = np.random.default_rng(0)
    for i in range(steps):
        start = i * chunk
        positions = (jnp.arange(chunk) + start)[None, :].repeat(n_rows, 0)
        tokens = jnp.asarray(rng.integers(4, 40, (n_rows, chunk)), jnp.int32)
        ld, dense = step(dense, tokens, positions)
        lp, paged = step(paged, tokens, positions)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                   rtol=2e-5, atol=2e-5)
        d, p = _self_node(dense), _self_node(paged)
        pos = np.arange(start + chunk)
        tab = np.asarray(p.block_tables[0])
        for layer in range(R):
            for kv_d, kv_p in ((d.k, p.k_pool), (d.v, p.v_pool)):
                want = np.asarray(kv_d[layer][:, pos])  # (B, S, n_kv, hd)
                got = np.asarray(kv_p[layer])[tab[:, pos // ps], pos % ps]
                np.testing.assert_allclose(
                    got, want.reshape(got.shape), rtol=2e-5, atol=2e-5)
        # beam reorder: rows now share their parents' pages
        src = jnp.asarray(rng.integers(0, n_rows, n_rows), jnp.int32)
        dense, paged = gather_rows(dense, src), gather_rows(paged, src)
        nxt = start + chunk + np.arange(chunk)
        window = [(r, int(b)) for r in range(n_rows)
                  for b in np.unique(nxt // ps)]
        paged, next_page = _private_window_pages(paged, window, next_page)
    return next_page


def test_seq2seq_paged_decode_matches_dense():
    """The same decode_step chunks through a paged self-attn cache (block
    tables mapped by hand, then beam reorders and copy-on-write pages
    between steps) produce logits identical to the dense cache, and each
    layer's slice of the stacked pool holds that layer's K/V — the
    models-layer half of the paged/dense token-identity contract (the
    session/engine half lives in tests/test_session.py)."""
    from repro.configs.mt import tiny_config
    cfg = tiny_config(48, depth=2, d_model=64)
    key = jax.random.PRNGKey(5)
    params = s2s.init(key, cfg)
    B, S, ps, max_len, chunk, steps = 3, 14, 4, 32, 3, 5
    src = jax.random.randint(key, (B, S), 4, cfg.vocab_size)
    memory, src_mask = s2s.encode(params, cfg, src)
    n_blocks = max_len // ps
    dense = s2s.init_cache(cfg, B, max_len=max_len, memory=memory,
                           params=params, memory_mask=src_mask)
    paged = s2s.init_cache(cfg, B, max_len=max_len, memory=memory,
                           params=params, memory_mask=src_mask,
                           paged=(B * n_blocks + 1 + B * 2 * steps, ps))

    def step(cache, tokens, positions):
        return s2s.decode_step(params, cfg, cache, tokens, positions)

    _paged_matches_dense(step, dense, paged, B, steps, chunk, ps)


def test_decoder_only_paged_decode_matches_dense():
    """The decoder-only half of the same contract (GQA: 3 query heads over
    one KV head, rope): paged decode through ``transformer.decode_step``
    with beam reorders and copy-on-write pages between steps matches the
    dense cache step for step, layer for layer."""
    cfg = get_config("smollm-135m", reduced=True)
    params = tr.init(jax.random.PRNGKey(3), cfg)
    B, ps, max_len, chunk, steps = 3, 4, 32, 3, 5
    n_blocks = max_len // ps
    dense = tr.init_cache(cfg, B, max_len)
    paged = tr.init_cache(cfg, B, max_len,
                          paged=(B * n_blocks + 1 + B * 2 * steps, ps))

    def step(cache, tokens, positions):
        return tr.decode_step(params, cfg, cache, tokens, positions)

    _paged_matches_dense(step, dense, paged, B, steps, chunk, ps)


def test_sliding_window_variant_matches_full_within_window():
    """The beyond-paper sliding-window variant: ring-buffer cached decode
    equals full apply when the context fits the window."""
    cfg = dataclasses.replace(get_config("smollm-135m", reduced=True),
                              sliding_window=8)
    key = jax.random.PRNGKey(7)
    params = tr.init(key, cfg)
    toks = jax.random.randint(key, (1, 12), 4, cfg.vocab_size)
    full, _ = tr.apply(params, cfg, toks)

    cache = tr.init_cache(cfg, 1, max_len=64)  # ring buffer of size 8
    assert cache[0].k.shape[2] == 8  # (repeats, B, S=window, kv, hd)
    _, cache = tr.prefill(params, cfg, cache, toks[:, :4])
    for t in range(4, 12):
        logits, cache = tr.decode_step(
            params, cfg, cache, toks[:, t : t + 1],
            jnp.array([[t]], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)
