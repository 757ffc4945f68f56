"""GQA decode attention over a long KV cache — the speculative-verify
hot spot (DL+1 query rows per sequence against S cached keys).

TPU adaptation of the paper's GPU verify pass: instead of inflating the
batch and re-reading the KV cache once per query row, the q-head group of
each KV head rides the *sublane* dimension — all T*G query rows are scored
against each streamed (bk, hd) KV tile in one MXU matmul, so every KV byte
is read exactly once per group, not per head. Grid (B, Kv, S/bk), sequential
kv dimension with online-softmax scratch, masking on the stored-position
array (ring-buffer/sliding-window semantics identical to
models.attention.cached_attention).

Mosaic tiles the last two dims of every block by (8, 128) unless a block
dim spans the whole array dim. So the position arrays carry a unit middle
axis — key positions ``(., 1, S)`` ride lanes, query positions
``(B, T*G, 1)`` ride sublanes, one row per query row of the q tile (the
wrapper expands T positions to T*G rows) — and every block's last two dims
are either whole or 128-aligned.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, kpos_ref, qpos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bk: int, kv_blocks: int,
                   window: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (T*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    TG = q.shape[0]
    kp_b = jnp.broadcast_to(kpos_ref[0], (TG, bk))       # from (1, bk)
    qp_rows = jnp.broadcast_to(qpos_ref[0], (TG, bk))    # from (TG, 1)
    mask = (kp_b >= 0) & (kp_b <= qp_rows)
    if window > 0:
        mask &= kp_b > qp_rows - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)  # fully-masked tiles contribute nothing
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_gqa_kernel(q_r, k_r, v_r, k_pos, q_rows, *, window: int = 0,
                      bk: int = 128, interpret: bool):
    """q_r: (B, Kv, T*G, hd); k_r/v_r: (B, Kv, S, hd); k_pos: (B, 1, S);
    q_rows: (B, T*G, 1), the position of each query row. S % bk == 0.
    Returns (B, Kv, T*G, hd)."""
    B, Kv, TG, hd = q_r.shape
    S = k_r.shape[2]
    kv_blocks = S // bk
    kernel = functools.partial(_decode_kernel, bk=bk, kv_blocks=kv_blocks,
                               window=window, scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kernel,
        grid=(B, Kv, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, TG, hd), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, g, ki: (b, 0, ki)),
            pl.BlockSpec((1, TG, 1), lambda b, g, ki: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TG, hd), lambda b, g, ki: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kv, TG, hd), q_r.dtype),
        scratch_shapes=[
            pltpu.VMEM((TG, 1), jnp.float32),
            pltpu.VMEM((TG, 1), jnp.float32),
            pltpu.VMEM((TG, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_r, k_r, v_r, k_pos, q_rows)


# ---------------------------------------------------------------------------
# paged variant: walk a block table instead of a contiguous row


def _paged_decode_kernel(bt_ref, q_ref, k_ref, v_ref, kpos_ref, qpos_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, ps: int,
                         n_blocks: int, window: int, scale: float):
    """One (sequence b, kv-head g, logical block j) grid step. The block
    table rides scalar prefetch: the K/V BlockSpecs DMA page
    ``bt[b, j]`` of the *pool* directly — the kernel never materializes the
    per-row gathered view the XLA path builds, so HBM traffic is one pool
    page per grid step regardless of how rows alias pages."""
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (T*G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    mapped = bt_ref[b, j] >= 0                            # unmapped -> page 0
    TG = q.shape[0]
    kp_b = jnp.broadcast_to(kpos_ref[0], (TG, ps))       # from (1, ps)
    qp_rows = jnp.broadcast_to(qpos_ref[0], (TG, ps))    # from (TG, 1)
    mask = mapped & (kp_b >= 0) & (kp_b <= qp_rows)
    if window > 0:
        mask &= kp_b > qp_rows - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)  # fully-masked tiles contribute nothing
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_gqa_kernel(block_tables, q_r, k_pool, v_pool, pos_pool,
                            q_rows, *, window: int = 0, interpret: bool):
    """q_r: (B, Kv, T*G, hd); k/v_pool: (P, Kv, ps, hd); pos_pool:
    (P, 1, ps); block_tables: (B, n_blocks) int32 page ids (-1 unmapped);
    q_rows: (B, T*G, 1), the position of each query row. Returns
    (B, Kv, T*G, hd). One KV tile = one page (bk == page_size)."""
    B, Kv, TG, hd = q_r.shape
    ps = k_pool.shape[2]
    n_blocks = block_tables.shape[1]
    kernel = functools.partial(_paged_decode_kernel, ps=ps,
                               n_blocks=n_blocks, window=window,
                               scale=1.0 / math.sqrt(hd))

    def page(b, g, j, bt):   # data-dependent DMA: the block-table walk
        return (jnp.maximum(bt[b, j], 0), g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Kv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, TG, hd), lambda b, g, j, bt: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), page),
            pl.BlockSpec((1, 1, ps, hd), page),
            pl.BlockSpec((1, 1, ps),
                         lambda b, g, j, bt: (jnp.maximum(bt[b, j], 0), 0, 0)),
            pl.BlockSpec((1, TG, 1), lambda b, g, j, bt: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TG, hd),
                               lambda b, g, j, bt: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((TG, 1), jnp.float32),
            pltpu.VMEM((TG, 1), jnp.float32),
            pltpu.VMEM((TG, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, TG, hd), q_r.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, q_r, k_pool, v_pool, pos_pool, q_rows)
