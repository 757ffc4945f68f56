"""Pure-jnp oracle for GQA decode attention over a position-tagged KV cache.

Mirrors ``repro.models.attention.cached_attention`` masking semantics:
slot validity comes from the stored-position array (-1 = empty), causality
from q_pos >= k_pos, and the optional sliding window from k_pos > q_pos - W.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_decode_gqa_ref(q, k_pool, v_pool, pos_pool, block_tables, q_pos,
                         *, window: int = 0):
    """Paged oracle: gather each row's mapped pages into the dense view,
    then run the dense oracle (mirrors ``models.attention.paged_view``).

    q: (B, T, H, hd); k/v_pool: (P, ps, Kv * hd), heads folded; pos_pool:
    (P, ps); block_tables: (B, n_blocks) int32 page ids, -1 unmapped.
    Returns (B, T, H, hd)."""
    B, nb = block_tables.shape
    ps, hd = k_pool.shape[1], q.shape[-1]
    pages = jnp.where(block_tables >= 0, block_tables, 0)
    k = k_pool[pages].reshape(B, nb * ps, -1, hd)
    v = v_pool[pages].reshape(B, nb * ps, -1, hd)
    kpos = jnp.where(block_tables[..., None] >= 0, pos_pool[pages], -1)
    return decode_gqa_ref(q, k, v, kpos.reshape(B, nb * ps), q_pos,
                          window=window)


def decode_gqa_ref(q, k_cache, v_cache, k_pos, q_pos, *, window: int = 0):
    """q: (B, T, H, hd); k/v_cache: (B, S, Kv, hd); k_pos: (B, S);
    q_pos: (B, T). Returns (B, T, H, hd)."""
    B, T, H, hd = q.shape
    Kv = k_cache.shape[2]
    G = H // Kv
    qr = q.reshape(B, T, Kv, G, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qr.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(hd)
    kp = k_pos[:, None, None, None, :]
    qp = q_pos[:, None, None, :, None]
    mask = (kp >= 0) & (kp <= qp)
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (no valid keys) -> zeros, matching the kernel guard
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    w = jnp.where(any_valid, w, 0.0)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v_cache.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)
