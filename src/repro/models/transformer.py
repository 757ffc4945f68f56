"""Decoder-only (and encoder-only) transformer assembled from a repeating
layer-block pattern — one implementation covers all ten assigned families:

  dense GQA (command-r, qwen3, starcoder2, smollm) : pattern ("attn",)
  VLM (llama-3.2-vision)  : ("attn",)*4 + ("xattn",)  — cross-attn every 5th
  hybrid (jamba)          : mamba/attn 7:1 block with MoE every other layer
  MoE (llama4, phi3.5)    : ("attn",) with ffn_pattern "moe"
  SSM (rwkv6)             : ("rwkv",)
  audio encoder (hubert)  : ("attn",), causal=False, embeddings input

The layer stack is a ``lax.scan`` over pattern repeats (stacked params), so
HLO size and compile time are depth-independent — a hard requirement for the
40× multi-pod dry-run on the CPU host.

Decode caches & speculative decoding
------------------------------------
``decode_step`` feeds T = DL+1 tokens (last committed token + draft) and
returns a cache with *per-step checkpoints* for recurrent blocks. The caller
commits the accepted prefix with ``commit_cache(cfg, cache, n_keep)`` where
``n_keep (B,)`` = 1 + accepted draft tokens. Attention KV caches need no
rollback: stale slots (rejected drafts) are always overwritten by the next
verify pass before they can be attended to (positions are masked on the
stored-position array). Recurrent state rollback is the honest cost of the
paper's technique on SSM/hybrid families (DESIGN.md §4).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.attention import attention, cached_attention, cross_attention
from repro.models.layers import (
    apply_norm, dense, embed, embed_init, ffn, ffn_init, logits_init, norm_init,
    sinusoidal_positions, unembed,
)
from repro.sharding import ctx as shard_ctx


class DecodeContext(NamedTuple):
    """Static per-call context threaded through block application."""
    mode: str                    # "full" | "prefill" | "decode"
    causal: bool = True
    memory: Any = None           # (B, M, memory_dim) frontend embeddings
    memory_mask: Any = None      # (B, M) bool
    lengths: Any = None          # (B,) row lengths (prefill/full with padding)
    positions: Any = None        # (B, T) absolute positions


# ---------------------------------------------------------------------------
# init


def _ffn_init(key, cfg: ModelConfig, kind: str, dtype):
    if kind == "moe":
        return moe_mod.moe_init(key, cfg, dtype=dtype)
    return ffn_init(key, cfg.d_model, cfg.d_ff, use_bias=cfg.use_bias,
                    gated=cfg.gated_ffn, dtype=dtype)


def _block_init(key, cfg: ModelConfig, kind: str, ffn_kind: str, dtype):
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    p: dict = {"norm1": norm_init(d, cfg.norm, dtype)}
    if kind == "attn":
        p["attn"] = attn_mod.attn_init(k1, cfg, dtype=dtype)
    elif kind == "xattn":
        p["attn"] = attn_mod.attn_init(k1, cfg, cross=True, dtype=dtype)
        p["xattn_gate"] = jnp.zeros((1,), dtype)  # llama-3.2 gated cross-attn
    elif kind == "mamba":
        p["mamba"] = mamba_mod.mamba_init(k1, cfg, dtype=dtype)
    elif kind == "rwkv":
        p["rwkv"] = rwkv_mod.rwkv_init(k1, cfg, dtype=dtype)
        p["norm2"] = norm_init(d, cfg.norm, dtype)
        p["cmix"] = rwkv_mod.rwkv_channel_init(k2, cfg, dtype=dtype)
        return p
    else:
        raise ValueError(kind)
    p["norm2"] = norm_init(d, cfg.norm, dtype)
    p["ffn"] = _ffn_init(k2, cfg, ffn_kind, dtype)
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, len(cfg.layer_pattern) + 3)
    params: dict = {}
    if cfg.family != "audio":  # audio consumes frontend embeddings directly
        params["tok"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)
    blocks = []
    for i, kind in enumerate(cfg.layer_pattern):
        rep_keys = jax.random.split(keys[1 + i], cfg.n_repeats)
        blocks.append(
            jax.vmap(partial(_block_init, cfg=cfg, kind=kind,
                             ffn_kind=cfg.ffn_pattern[i], dtype=dtype))(rep_keys)
        )
    params["blocks"] = tuple(blocks)
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = logits_init(keys[-1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32,
               paged=None) -> tuple:
    """Per-pattern-position caches, stacked over repeats (leading axis).

    ``paged``: ``(n_pages, page_size)`` — allocate every "attn" position's
    self-attention cache as a ``PagedKVCache`` (one pool per position,
    stacked over repeats to ``(n_repeats, n_pages, page_size, n_kv_heads *
    head_dim)``) instead of dense rows; the caller owns page
    mapping (``repro.core.session.PageAllocator``). All paged positions
    share one page-id space: the allocator keeps their block tables
    identical, so a page id addresses the same logical block in every
    position's pool. Recurrent (mamba/rwkv) and cross-attn caches stay
    dense — their per-row state is O(1) in sequence length or written once.
    """

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.n_repeats,) + a.shape), tree)

    caches = []
    for kind in cfg.layer_pattern:
        if kind == "attn":
            if paged is not None:
                n_pages, page_size = paged
                c = attn_mod.init_paged_kv_cache(
                    cfg, batch, max_len, n_pages=n_pages,
                    page_size=page_size, dtype=dtype)
            else:
                c = attn_mod.init_kv_cache(cfg, batch, max_len, dtype=dtype)
        elif kind == "xattn":
            M = max(cfg.memory_tokens, 1)
            c = {"mk": jnp.zeros((batch, M, cfg.n_heads, cfg.head_dim), dtype),
                 "mv": jnp.zeros((batch, M, cfg.n_heads, cfg.head_dim), dtype)}
        elif kind == "mamba":
            c = mamba_mod.init_mamba_cache(cfg, batch, dtype)
        elif kind == "rwkv":
            H, hd = cfg.d_model // cfg.rwkv.head_dim, cfg.rwkv.head_dim
            c = {"S": jnp.zeros((batch, H, hd, hd), jnp.float32),
                 "x_tm": jnp.zeros((batch, cfg.d_model), dtype),
                 "x_cm": jnp.zeros((batch, cfg.d_model), dtype)}
        else:
            raise ValueError(kind)
        caches.append(stack(c))
    return tuple(caches)


def commit_cache(cfg: ModelConfig, cache: tuple, n_keep) -> tuple:
    """Select recurrent-state checkpoints after speculative verification.

    n_keep: (B,) int32 — number of fed tokens accepted per row (>= 1).
    Checkpointed recurrent leaves have shape (R, B, T+1, ...); we take index
    n_keep along the step axis. Attention/xattn caches pass through.
    """
    idx = jnp.asarray(n_keep, jnp.int32)

    def take_ckpt(a):
        # a: (R, B, T+1, ...) -> (R, B, ...)
        ix = idx.reshape((1,) + idx.shape + (1,) * (a.ndim - 2))
        return jnp.take_along_axis(a, ix.astype(jnp.int32), axis=2).squeeze(2)

    out = []
    for kind, c in zip(cfg.layer_pattern, cache):
        if kind in ("attn", "xattn"):
            out.append(c)
        else:
            out.append(jax.tree_util.tree_map(take_ckpt, c))
    return tuple(out)


# ---------------------------------------------------------------------------
# block application


def _apply_ffn(p, cfg: ModelConfig, kind: str, x):
    if kind == "moe":
        return moe_mod.moe_ffn(p, cfg, x)
    return ffn(p, x), {}


def _mamba_decode_ckpt(p, cfg, cache, x):
    """Sequential decode that also emits per-step state checkpoints."""
    T = x.shape[1]
    conv0, ssm0 = cache["conv"], cache["ssm"]
    convs, ssms = [conv0], [ssm0]
    c = cache
    ys = []
    for t in range(T):  # T = DL+1 is small & static: unrolled is cheapest
        y, c = mamba_mod.mamba_step(p, cfg, c, x[:, t : t + 1, :])
        ys.append(y)
        convs.append(c["conv"])
        ssms.append(c["ssm"])
    out = jnp.concatenate(ys, axis=1)
    ckpt = {"conv": jnp.stack(convs, axis=1), "ssm": jnp.stack(ssms, axis=1)}
    return out, ckpt


def _rwkv_decode_ckpt(p, cfg, cache, x):
    T = x.shape[1]
    S, x_tm, x_cm = cache["S"], cache["x_tm"], cache["x_cm"]
    Ss, xtms, xcms = [S], [x_tm], [x_cm]
    outs = []
    h = x
    for t in range(T):
        xt = h[:, t : t + 1, :]
        n1 = apply_norm_block(p["norm1"], xt, cfg)
        mix_out, (S, x_tm_new) = rwkv_mod.rwkv_mixer(p["rwkv"], cfg, n1, state=S,
                                                     x_last=x_tm)
        x_tm = x_tm_new
        xt = xt + mix_out
        n2 = apply_norm_block(p["norm2"], xt, cfg)
        cm_out, x_cm = rwkv_mod.rwkv_channel_mix(p["cmix"], n2, x_last=x_cm)
        xt = xt + cm_out
        outs.append(xt)
        Ss.append(S)
        xtms.append(x_tm)
        xcms.append(x_cm)
    out = jnp.concatenate(outs, axis=1)
    ckpt = {"S": jnp.stack(Ss, axis=1), "x_tm": jnp.stack(xtms, axis=1),
            "x_cm": jnp.stack(xcms, axis=1)}
    return out, ckpt


def apply_norm_block(p, x, cfg: ModelConfig):
    return apply_norm(p, x, cfg.norm)


def _block_apply(kind: str, ffn_kind: str, p, cfg: ModelConfig, x, cache,
                 dctx: DecodeContext, layer=None):
    """One layer. Returns (x, new_cache, aux_losses). A paged "attn" cache
    is the whole stack over repeats, addressed at repeat ``layer``."""
    aux: dict = {}
    if kind == "rwkv":
        if dctx.mode == "decode":
            return _rwkv_decode_ckpt(p, cfg, cache, x) + (aux,)
        # full / prefill: chunk-free scan over the whole sequence
        n1 = apply_norm_block(p["norm1"], x, cfg)
        if dctx.lengths is not None:  # zero pad positions so state skips them
            valid = (jnp.arange(x.shape[1]) < dctx.lengths[:, None])
            n1 = n1 * valid[..., None].astype(n1.dtype)
        mix_out, (S, _) = rwkv_mod.rwkv_mixer(
            p["rwkv"], cfg, n1,
            state=None if cache is None else cache["S"],
            x_last=None if cache is None else cache["x_tm"],
            lengths=dctx.lengths)
        x = x + mix_out
        n2 = apply_norm_block(p["norm2"], x, cfg)
        cm_out, _ = rwkv_mod.rwkv_channel_mix(p["cmix"], n2)
        x = x + cm_out
        new_cache = None
        if cache is not None:  # prefill: gather per-row final states
            L = dctx.lengths if dctx.lengths is not None else jnp.full(
                (x.shape[0],), x.shape[1], jnp.int32)
            last = jnp.clip(L - 1, 0, x.shape[1] - 1)
            gather = lambda seq: jnp.take_along_axis(
                seq, last[:, None, None].astype(jnp.int32), axis=1).squeeze(1)
            new_cache = {"S": S, "x_tm": gather(n1), "x_cm": gather(n2)}
        return x, new_cache, aux

    h = apply_norm_block(p["norm1"], x, cfg)
    if kind == "attn":
        if dctx.mode == "full":
            a = attention(p["attn"], cfg, h, positions=dctx.positions,
                          causal=dctx.causal,
                          padding_mask=None if dctx.lengths is None else
                          (jnp.arange(h.shape[1]) < dctx.lengths[:, None]))
            new_cache = None
        else:
            a, new_cache = cached_attention(p["attn"], cfg, h, cache,
                                            dctx.positions, layer=layer)
        x = x + a
    elif kind == "xattn":
        if dctx.mode == "decode":
            q = attn_mod.cached_cross_attention(p["attn"], cfg, h, cache,
                                                memory_mask=dctx.memory_mask)
            new_cache = cache
        else:
            q = cross_attention(p["attn"], cfg, h, dctx.memory,
                                memory_mask=dctx.memory_mask)
            new_cache = (attn_mod.memory_kv(p["attn"], cfg, dctx.memory)
                         if dctx.mode == "prefill" else None)
        x = x + jnp.tanh(p["xattn_gate"]) * q
    elif kind == "mamba":
        if dctx.mode == "decode":
            m_out, new_cache = _mamba_decode_ckpt(p["mamba"], cfg, cache, h)
        elif dctx.mode == "prefill":
            m_out, new_cache = mamba_mod.mamba_mixer(
                p["mamba"], cfg, h, lengths=dctx.lengths, return_state=True)
        else:
            m_out = mamba_mod.mamba_mixer(p["mamba"], cfg, h, lengths=dctx.lengths)
            new_cache = None
        x = x + m_out
    else:
        raise ValueError(kind)

    h2 = apply_norm_block(p["norm2"], x, cfg)
    f_out, aux = _apply_ffn(p["ffn"], cfg, ffn_kind, h2)
    x = x + f_out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stack


# Layer-scan unroll factor. The multi-pod dry-run sets this to a full unroll:
# (a) XLA's cost analysis counts while-loop bodies once, so a rolled scan
# underreports FLOPs by ~n_repeats; (b) GSPMD hoists the FSDP all-gather of
# the *stacked* layer weights out of the loop, inflating temp memory by the
# full unsharded parameter size. Unrolled, gathers happen per layer and are
# freed. Training-time default stays rolled (compile-time friendly).
SCAN_UNROLL: int | bool = 1


def _run_stack(params, cfg: ModelConfig, x, cache, dctx: DecodeContext,
               *, remat: bool = False):
    aux_keys = ("moe_aux_loss", "moe_z_loss") if "moe" in cfg.ffn_pattern else ()
    # Paged pools ride in the carry, written and read at [repeat, ...] in
    # place; every other cache is sliced per repeat (scan xs/ys).
    caches = (None,) * len(cfg.layer_pattern) if cache is None else cache
    paged = tuple(isinstance(c, attn_mod.PagedKVCache) for c in caches)
    pools = tuple(c if p else None for c, p in zip(caches, paged))
    sliced = tuple(None if p else c for c, p in zip(caches, paged))

    def repeat_body(carry, xs):
        h, pools = carry
        p_tuple, c_tuple, rep = xs
        new_pools, new_caches = [], []
        aux_sum = {k: jnp.float32(0) for k in aux_keys}
        for i, kind in enumerate(cfg.layer_pattern):
            c_i = pools[i] if paged[i] else c_tuple[i]
            h, nc, aux = _block_apply(kind, cfg.ffn_pattern[i], p_tuple[i],
                                      cfg, h, c_i, dctx, layer=rep)
            h = shard_ctx.constrain_activation(h)
            new_pools.append(nc if paged[i] else None)
            new_caches.append(None if paged[i] else nc)
            for k in aux_keys:
                aux_sum[k] = aux_sum[k] + aux.get(k, 0.0)
        return (h, tuple(new_pools)), (tuple(new_caches), aux_sum)

    body = jax.checkpoint(repeat_body) if remat else repeat_body
    reps = jnp.arange(cfg.n_repeats, dtype=jnp.int32)
    (x, pools), (new_sliced, aux_per_rep) = jax.lax.scan(
        body, (x, pools), (params["blocks"], sliced, reps),
        unroll=SCAN_UNROLL)
    new_cache = tuple(pl if p else c
                      for pl, c, p in zip(pools, new_sliced, paged))
    aux = {k: jnp.sum(v) for k, v in aux_per_rep.items()}
    return x, new_cache, aux


def _embed_in(params, cfg: ModelConfig, tokens, embeddings):
    if embeddings is not None:
        return embeddings
    return embed(params["tok"], tokens)


def _logits_out(params, cfg: ModelConfig, x):
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        return unembed(params["tok"], x)
    return x @ params["lm_head"]["w_vocab"]


# ---------------------------------------------------------------------------
# public API


def apply(params, cfg: ModelConfig, tokens=None, *, embeddings=None, memory=None,
          memory_mask=None, lengths=None, positions=None, causal=None,
          remat: bool = False):
    """Full-sequence forward (training). Returns (logits, aux)."""
    x = _embed_in(params, cfg, tokens, embeddings)
    B, T = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    dctx = DecodeContext(mode="full", causal=cfg.causal if causal is None else causal,
                         memory=memory, memory_mask=memory_mask, lengths=lengths,
                         positions=positions)
    x = shard_ctx.constrain_activation(x)
    x, _, aux = _run_stack(params, cfg, x, None, dctx, remat=remat)
    return _logits_out(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, cache, tokens=None, *, embeddings=None,
            memory=None, memory_mask=None, lengths=None,
            logits_mode: str = "all"):
    """Process the prompt, filling caches. Returns (logits, cache).

    ``logits_mode="last"`` computes logits only at each row's final valid
    position — (B, V) instead of (B, T, V). At 32k prompt × 256k vocab the
    full tensor would be half a terabyte; serving never needs it.
    """
    x = _embed_in(params, cfg, tokens, embeddings)
    B, T = x.shape[:2]
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    positions = jnp.where(pos < lengths[:, None], pos, -1)  # pads -> masked slot
    dctx = DecodeContext(mode="prefill", causal=True, memory=memory,
                         memory_mask=memory_mask, lengths=lengths,
                         positions=positions)
    x, new_cache, _ = _run_stack(params, cfg, x, cache, dctx)
    if logits_mode == "last":
        last = jnp.clip(lengths - 1, 0, T - 1)
        x = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32),
                                axis=1)[:, 0]
        return _logits_out(params, cfg, x), new_cache
    return _logits_out(params, cfg, x), new_cache


def multidraft_verify_step(params, cfg: ModelConfig, cache, tokens, positions,
                           local_mask, *, memory_mask=None):
    """Single-pass verification of ALL drafts (beyond-paper; see
    attention.multidraft_attention). tokens: (B, 1 + N_d·DL) =
    [last_committed, draft_0…, draft_{N_d-1}…]; positions: their logical
    absolute positions; local_mask: static (T, T) segment mask.

    Attention-family blocks only (dense/MoE/VLM): recurrent mixers process
    tokens sequentially, so multi-draft segments cannot share a row —
    those archs use the expanded-batch path (DESIGN.md §4).

    Returns (logits, local_kv) where local_kv is a tuple (one per attn
    pattern position) of (k_new, v_new) stacked over scan repeats — feed it
    to ``commit_multidraft``. The cache is NOT modified.
    """
    for kind in cfg.layer_pattern:
        if kind in ("mamba", "rwkv"):
            raise NotImplementedError(
                "multidraft verification needs attention blocks; recurrent "
                "families use the expanded-batch verify path")
    x = _embed_in(params, cfg, tokens, None)

    def repeat_body(h, xs):
        p_tuple, c_tuple = xs
        kvs = []
        for i, kind in enumerate(cfg.layer_pattern):
            p = p_tuple[i]
            h1 = apply_norm_block(p["norm1"], h, cfg)
            if kind == "attn":
                a, kv = attn_mod.multidraft_attention(
                    p["attn"], cfg, h1, c_tuple[i], positions, local_mask)
                h = h + a
                kvs.append(kv)
            elif kind == "xattn":
                qo = attn_mod.cached_cross_attention(
                    p["attn"], cfg, h1, c_tuple[i], memory_mask=memory_mask)
                h = h + jnp.tanh(p["xattn_gate"]) * qo
                kvs.append((jnp.zeros((0,)), jnp.zeros((0,))))
            h2 = apply_norm_block(p["norm2"], h, cfg)
            f_out, _ = _apply_ffn(p["ffn"], cfg, cfg.ffn_pattern[i], h2)
            h = h + f_out
        return h, tuple(kvs)

    x, local_kv = jax.lax.scan(repeat_body, x,
                               (params["blocks"], cache), unroll=SCAN_UNROLL)
    return _logits_out(params, cfg, x), local_kv


def commit_multidraft(cfg: ModelConfig, cache, local_kv, best, n_acc,
                      start_pos, *, draft_len: int):
    """Write the winning draft's accepted K/V into the cache.

    best: (B,) winning draft index; n_acc: (B,) accepted draft tokens;
    start_pos: (B,) position of the fed last_committed token. Commits the
    last token + n_acc accepted draft tokens (n_keep = 1 + n_acc), exactly
    mirroring the expanded-batch invariant."""
    B = best.shape[0]
    DL = draft_len
    rel = jnp.arange(DL + 1, dtype=jnp.int32)
    # local indices: 0 (last_tok), then winner segment 1 + best*DL + i
    take_idx = jnp.concatenate(
        [jnp.zeros((B, 1), jnp.int32),
         1 + best[:, None] * DL + rel[None, :-1]], axis=1)       # (B, DL+1)
    positions = start_pos[:, None] + rel[None, :]
    n_keep = 1 + n_acc
    out = []
    for kind, c, kv in zip(cfg.layer_pattern, cache, local_kv):
        if kind == "attn":
            def one(cc, kk, vv):
                return attn_mod.commit_verified_kv(cc, kk, vv, take_idx,
                                                   positions, n_keep)
            out.append(jax.vmap(one)(c, kv[0], kv[1]))
        else:
            out.append(c)
    return tuple(out)


def decode_step(params, cfg: ModelConfig, cache, tokens, positions, *,
                memory_mask=None):
    """Decode T new tokens (T = 1 for plain greedy, DL+1 for verification).

    positions: (B, T) absolute positions of the fed tokens (rows may differ).
    Returns (logits (B,T,V), cache-with-checkpoints) — call ``commit_cache``.
    """
    x = _embed_in(params, cfg, tokens, None)
    dctx = DecodeContext(mode="decode", causal=True, memory_mask=memory_mask,
                         positions=positions)
    x, new_cache, _ = _run_stack(params, cfg, x, cache, dctx)
    return _logits_out(params, cfg, x), new_cache
