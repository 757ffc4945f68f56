"""jit-able wrappers matching the model cache layouts: dense (B, S, Kv, hd)
rows and the ``PagedKVCache`` pool/block-table pair, whose pages hold
(ps, Kv * hd) rows with the heads folded."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_gqa.kernel import (decode_gqa_kernel,
                                             paged_decode_gqa_kernel)
from repro.kernels.platform import interpret_mode


def _split_heads(q, Kv):
    """(B, T, H, hd) -> (B, Kv, T*G, hd): the q-head group rides sublanes."""
    B, T, H, hd = q.shape
    G = H // Kv
    return q.reshape(B, T, Kv, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Kv, T * G, hd)


def _row_positions(q_pos, G):
    """(B, T) -> (B, T*G, 1): each query row's position, in _split_heads'
    row order, as a sublane column."""
    return jnp.repeat(q_pos, G, axis=1)[..., None]


def _merge_heads(out, T):
    B, Kv, TG, hd = out.shape
    G = TG // T
    return out.reshape(B, Kv, T, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, T, Kv * G, hd)


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_gqa_attention(q, k_pool, v_pool, pos_pool, block_tables,
                               q_pos, *, window: int = 0,
                               interpret: bool | None = None):
    """Paged decode attention: walk the block table, one DMA per mapped
    page — no materialized per-row gather (the XLA fallback builds the
    (B, n_blocks*ps, ...) view; at serving batch sizes that copy dwarfs the
    attention math).

    q: (B, T, H, hd); k/v_pool: (P, ps, Kv * hd) (the ``PagedKVCache`` pool
    layout for one layer, heads folded); pos_pool: (P, ps) stored positions
    (-1 empty); block_tables: (B, n_blocks) page ids (-1 unmapped); q_pos:
    (B, T). Returns (B, T, H, hd)."""
    B, T, H, hd = q.shape
    P, ps, F = k_pool.shape
    Kv = F // hd
    q_r = _split_heads(q, Kv)
    # the kernel tiles one page per head: heads split at its boundary
    k_r = k_pool.reshape(P, ps, Kv, hd).transpose(0, 2, 1, 3)  # (P, Kv, ps, hd)
    v_r = v_pool.reshape(P, ps, Kv, hd).transpose(0, 2, 1, 3)
    out = paged_decode_gqa_kernel(
        block_tables.astype(jnp.int32), q_r, k_r, v_r, pos_pool[:, None],
        _row_positions(q_pos, H // Kv), window=window,
        interpret=interpret_mode(interpret))
    return _merge_heads(out, T)


@partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_gqa_attention(q, k_cache, v_cache, k_pos, q_pos, *,
                         window: int = 0, bk: int = 128,
                         interpret: bool | None = None):
    """q: (B, T, H, hd); k/v_cache: (B, S, Kv, hd); k_pos: (B, S) stored
    positions (-1 empty); q_pos: (B, T). Returns (B, T, H, hd)."""
    B, T, H, hd = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    bk = min(bk, max(8, S))
    Sp = ((S + bk - 1) // bk) * bk
    if Sp != S:
        pad = Sp - S
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    q_r = _split_heads(q, Kv)
    k_r = k_cache.transpose(0, 2, 1, 3)
    v_r = v_cache.transpose(0, 2, 1, 3)
    out = decode_gqa_kernel(q_r, k_r, v_r, k_pos[:, None],
                            _row_positions(q_pos, H // Kv), window=window,
                            bk=bk, interpret=interpret_mode(interpret))
    return _merge_heads(out, T)
