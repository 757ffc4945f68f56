"""Blocked flash attention (forward) as a Pallas TPU kernel.

Grid: (batch*heads, q_blocks, kv_blocks); the kv dimension is sequential
("arbitrary"), so VMEM scratch (running max m, normalizer l, accumulator
acc) persists across kv steps — the canonical TPU online-softmax layout.
Tiles: q (bq, hd), k/v (bk, hd); bq=bk=128 are MXU-aligned; hd rides the
lane dimension. The HBM->VMEM traffic per (q-block) is S/bk streamed K/V
tiles; the output block is written once, on the last kv step.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 bq: int, bk: int, causal: bool, window: int, scale: float,
                 kv_blocks: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_len
    if causal:
        mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           seq_len: int | None = None,
                           interpret: bool):
    """q,k,v: (BH, S, hd) with S % bq == S % bk == 0. Returns (BH, S, hd).
    ``seq_len``: true (unpadded) length — keys at or beyond it are masked."""
    BH, S, hd = q.shape
    kv_blocks = S // bk
    grid = (BH, S // bq, kv_blocks)
    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=1.0 / math.sqrt(hd), kv_blocks=kv_blocks,
        seq_len=S if seq_len is None else seq_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # normalizer
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
