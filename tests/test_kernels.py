"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles,
executed with interpret=True on CPU (the TPU is the deployment target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_gqa.ops import (decode_gqa_attention,
                                          paged_decode_gqa_attention)
from repro.kernels.decode_gqa.ref import decode_gqa_ref, paged_decode_gqa_ref
from repro.kernels.draft_verify.ops import draft_verify
from repro.kernels.draft_verify.ref import draft_verify_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash_attention


@pytest.mark.parametrize("shape", [(2, 3, 64, 32), (1, 2, 96, 16),
                                   (2, 2, 128, 64), (1, 1, 33, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 24)])
def test_flash_attention(shape, dtype, causal, window):
    B, H, S, hd = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in keys)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# decode_gqa


@pytest.mark.parametrize("cfg", [
    dict(B=2, T=5, H=8, Kv=2, S=64, hd=32, window=0),
    dict(B=1, T=1, H=4, Kv=4, S=100, hd=16, window=0),   # plain greedy step
    dict(B=2, T=11, H=8, Kv=4, S=96, hd=64, window=24),  # verify + window
    dict(B=3, T=3, H=6, Kv=1, S=40, hd=8, window=0),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa(cfg, dtype):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    B, T, H, Kv, S, hd = (cfg[k] for k in ("B", "T", "H", "Kv", "S", "hd"))
    q = jax.random.normal(keys[0], (B, T, H, hd), dtype)
    kc = jax.random.normal(keys[1], (B, S, Kv, hd), dtype)
    vc = jax.random.normal(keys[2], (B, S, Kv, hd), dtype)
    L = S // 2
    k_pos = jnp.where(jnp.arange(S)[None, :] < L,
                      jnp.arange(S)[None, :], -1).repeat(B, 0)
    q_pos = (L - 1 + jnp.arange(T))[None, :].repeat(B, 0)
    out = decode_gqa_attention(q, kc, vc, k_pos, q_pos,
                               window=cfg["window"], bk=32)
    ref = decode_gqa_ref(q, kc, vc, k_pos, q_pos, window=cfg["window"])
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_gqa_ring_buffer():
    """Sliding-window ring buffer: stored positions wrap modulo S."""
    B, T, H, Kv, S, hd, W = 1, 3, 4, 2, 32, 16, 32
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (B, T, H, hd))
    kc = jax.random.normal(keys[1], (B, S, Kv, hd))
    vc = jax.random.normal(keys[2], (B, S, Kv, hd))
    # cache that has wrapped: slot s holds position 40 - ((40 - s) % 32)…
    pos = 48 - ((48 - jnp.arange(S)) % S)
    k_pos = pos[None, :]
    q_pos = jnp.asarray([[48, 49, 50]])
    out = decode_gqa_attention(q, kc, vc, k_pos, q_pos, window=W, bk=32)
    ref = decode_gqa_ref(q, kc, vc, k_pos, q_pos, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def _random_paged_cache(rng, B, P, ps, nb, Kv, hd, *, n_mapped, dtype):
    """Rows map ``n_mapped`` distinct pages each (prefix-contiguous blocks),
    with ragged fill levels; the rest of the table is unmapped (-1). Pages
    hold (ps, Kv * hd) rows, heads folded, as ``PagedKVCache`` stores
    them."""
    keys = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 2)
    k_pool = jax.random.normal(keys[0], (P, ps, Kv * hd), dtype)
    v_pool = jax.random.normal(keys[1], (P, ps, Kv * hd), dtype)
    bt = np.full((B, nb), -1, np.int32)
    pages = rng.permutation(np.arange(1, P))[:B * n_mapped]
    bt[:, :n_mapped] = pages.reshape(B, n_mapped)
    pos_pool = np.full((P, ps), -1, np.int32)
    for b in range(B):
        for j in range(n_mapped):
            fill = int(rng.integers(1, ps + 1))
            pos_pool[bt[b, j], :fill] = j * ps + np.arange(fill)
    return k_pool, v_pool, jnp.asarray(pos_pool), jnp.asarray(bt)


@pytest.mark.parametrize("cfg", [
    dict(B=2, T=5, H=8, Kv=2, P=23, ps=16, nb=5, hd=32, window=0),
    dict(B=1, T=1, H=4, Kv=4, P=9, ps=8, nb=4, hd=16, window=0),    # greedy
    dict(B=2, T=11, H=8, Kv=4, P=31, ps=16, nb=6, hd=64, window=24),
    dict(B=3, T=3, H=6, Kv=1, P=16, ps=8, nb=4, hd=8, window=0),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_gqa(cfg, dtype):
    """Block-table-walking kernel == gather-based paged oracle, including
    unmapped blocks, ragged page fills, and sliding windows."""
    rng = np.random.default_rng(7)
    B, T, H, Kv, P, ps, nb, hd = (cfg[k] for k in
                                  ("B", "T", "H", "Kv", "P", "ps", "nb", "hd"))
    n_mapped = min(nb - 1, (P - 1) // B)
    k_pool, v_pool, pos_pool, bt = _random_paged_cache(
        rng, B, P, ps, nb, Kv, hd, n_mapped=n_mapped, dtype=dtype)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, T, H, hd), dtype)
    q_pos = jnp.asarray(
        np.tile(n_mapped * ps - 2 + np.arange(T), (B, 1)).astype(np.int32))
    out = paged_decode_gqa_attention(q, k_pool, v_pool, pos_pool, bt, q_pos,
                                     window=cfg["window"])
    ref = paged_decode_gqa_ref(q, k_pool, v_pool, pos_pool, bt, q_pos,
                               window=cfg["window"])
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_decode_gqa_matches_dense_kernel():
    """A paged cache holding the same tokens as a contiguous dense row must
    attend identically — the kernel-level statement of the paged/dense
    token-identity contract."""
    B, T, H, Kv, hd, ps, nb = 2, 4, 8, 2, 32, 8, 4
    S = ps * nb
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (B, T, H, hd))
    kc = jax.random.normal(keys[1], (B, S, Kv, hd))
    vc = jax.random.normal(keys[2], (B, S, Kv, hd))
    L = 19  # valid prefix per row
    k_pos = jnp.where(jnp.arange(S)[None, :] < L,
                      jnp.arange(S)[None, :], -1).repeat(B, 0)
    q_pos = (L - 1 + jnp.arange(T))[None, :].repeat(B, 0)
    # scatter the dense rows into a shuffled pool, page 0 reserved as trash
    rng = np.random.default_rng(5)
    pages = rng.permutation(np.arange(1, B * nb + 1))
    bt = jnp.asarray(pages.reshape(B, nb).astype(np.int32))
    P = B * nb + 1
    k_pool = jnp.zeros((P, ps, Kv * hd)).at[bt.reshape(-1)].set(
        kc.reshape(B * nb, ps, Kv * hd))
    v_pool = jnp.zeros((P, ps, Kv * hd)).at[bt.reshape(-1)].set(
        vc.reshape(B * nb, ps, Kv * hd))
    pos_pool = jnp.full((P, ps), -1, jnp.int32).at[bt.reshape(-1)].set(
        k_pos.reshape(B * nb, ps))
    dense = decode_gqa_attention(q, kc, vc, k_pos, q_pos, bk=ps)
    paged = paged_decode_gqa_attention(q, k_pool, v_pool, pos_pool, bt, q_pos)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# draft_verify


@pytest.mark.parametrize("N,T,V", [(6, 5, 700), (12, 11, 1024), (3, 1, 64),
                                   (4, 6, 50), (25, 11, 320)])
def test_draft_verify(N, T, V):
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (N, T, V))
    greedy = jnp.argmax(logits, -1)
    DL = T - 1
    drafts = jnp.where(jax.random.bernoulli(key, 0.7, (N, DL)),
                       greedy[:, :DL],
                       jax.random.randint(key, (N, DL), 0, V)).astype(jnp.int32)
    mask = jax.random.bernoulli(jax.random.PRNGKey(4), 0.8, (N,))
    t1, a1 = draft_verify(logits, drafts, mask, bv=128)
    t2, a2 = draft_verify_ref(logits, drafts, mask)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_draft_verify_matches_core_acceptance():
    """The fused kernel implements exactly the acceptance rule the decoder
    uses (core.speculative._accept_lengths)."""
    from repro.core.speculative import _accept_lengths
    key = jax.random.PRNGKey(5)
    B, N_d, DL, V = 2, 6, 4, 90
    logits = jax.random.normal(key, (B * N_d, DL + 1, V))
    drafts = jax.random.randint(key, (B, N_d, DL), 0, V)
    mask = jnp.ones((B, N_d), bool)
    toks, acc = draft_verify(logits, drafts.reshape(B * N_d, DL),
                             mask.reshape(-1), bv=128)
    greedy = toks.reshape(B, N_d, DL + 1)
    expected = _accept_lengths(greedy, drafts, mask)
    np.testing.assert_array_equal(np.asarray(acc).reshape(B, N_d),
                                  np.asarray(expected))
