"""Fused draft verification — the paper's accept-op as one Pallas kernel.

The verify pass produces logits of shape (B*N_d, DL+1, V); materializing a
full argmax over V in HBM and then prefix-matching on host/XLA costs an
extra HBM round-trip of the logits. Here the vocab axis is streamed through
VMEM in (bv)-wide tiles with a running (max, argmax) scratch per row; the
final tile compares the winning tokens against the draft and emits both the
greedy tokens and the accepted-prefix length. One pass over the logits,
nothing but (N, T) tokens + (N,) lengths leaves the chip.

Grid: (N, V/bv) — vocab dimension sequential ("arbitrary") so scratch
persists; rows parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _verify_kernel(logits_ref, drafts_ref, mask_ref, tok_ref, acc_ref,
                   m_ref, i_ref, *, bv: int, v_blocks: int, vocab: int,
                   T: int, dl: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        i_ref[...] = jnp.zeros_like(i_ref)

    x = logits_ref[0].astype(jnp.float32)                 # (T, bv)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, (T, bv), 1)
    x = jnp.where(col < vocab, x, _NEG)                   # mask padded vocab
    blk_max = jnp.max(x, axis=1, keepdims=True)           # (T, 1)
    blk_arg = (vi * bv + jnp.argmax(x, axis=1)[:, None]).astype(jnp.int32)
    better = blk_max > m_ref[...]
    m_ref[...] = jnp.where(better, blk_max, m_ref[...])
    i_ref[...] = jnp.where(better, blk_arg, i_ref[...])

    @pl.when(vi == v_blocks - 1)
    def _finalize():
        greedy = i_ref[...][:, 0]                          # (T,)
        tok_ref[0] = greedy
        if dl > 0:
            d = drafts_ref[0][:dl]                         # (DL,)
            match = (d == greedy[:-1]).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(match, axis=0))
        else:
            acc = jnp.int32(0)
        acc_ref[0, 0] = jnp.where(mask_ref[0, 0] > 0, acc, 0).astype(jnp.int32)


def draft_verify_kernel(logits, drafts, draft_mask, *, bv: int = 512,
                        interpret: bool):
    """logits: (N, T, Vp) (vocab padded to bv multiple, true size ``vocab``
    passed implicitly = Vp unless padded by ops); drafts: (N, T-1);
    draft_mask: (N, 1) int32. Returns (tokens (N, T), n_acc (N, 1))."""
    N, T, Vp = logits.shape
    v_blocks = Vp // bv
    dl = drafts.shape[1]
    if dl == 0:  # DL=0 control mode: feed a dummy column, ignore it
        drafts = jnp.zeros((N, 1), jnp.int32)
    kernel = functools.partial(_verify_kernel, bv=bv, v_blocks=v_blocks,
                               vocab=Vp, T=T, dl=dl)
    DLm = drafts.shape[1]
    return pl.pallas_call(
        kernel,
        grid=(N, v_blocks),
        in_specs=[
            pl.BlockSpec((1, T, bv), lambda n, vi: (n, 0, vi)),
            pl.BlockSpec((1, DLm), lambda n, vi: (n, 0)),
            pl.BlockSpec((1, 1), lambda n, vi: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, T), lambda n, vi: (n, 0)),
            pl.BlockSpec((1, 1), lambda n, vi: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, T), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits, drafts, draft_mask)
