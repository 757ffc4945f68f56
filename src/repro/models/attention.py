"""Grouped-query attention with a unified KV cache.

One attention implementation serves every mode in the framework:

  - full-sequence causal (training / prefill), optional sliding window
  - bidirectional (encoder-only: HuBERT, MT encoder)
  - cross-attention to a memory (MT decoder, VLM image layers)
  - cached decode with per-row absolute positions — the speculative-decoding
    verify pass feeds ``DL+1`` tokens per sequence in one call

KV-cache design (TPU-native): pre-allocated ``(B, S, n_kv, head_dim)`` buffers
plus a ``(B, S)`` int32 ``pos`` array holding the *absolute* position stored in
each slot (-1 = empty). Writes go to ``slot = position % S``; masking is done
on stored positions, which makes a ring buffer (sliding window, ``S = window``)
and a linear cache (``S = max_len``) the same code path.

Paged variant (serving): ``PagedKVCache`` replaces the per-row ``(B, S)``
reservation with a global page pool ``(n_pages, page_size, n_kv * head_dim)``
(heads folded into one lane-dense minor axis) plus a per-row block table
``(B, n_blocks)`` of page ids (-1 = unmapped).
Rows of one request share read-only committed pages (the host allocator in
``repro.core.session.PageAllocator`` copy-on-writes the draft-boundary page),
so HBM scales with *live tokens*, not ``n_rows * max_len``. Page 0 is a
reserved trash page: writes whose target block is unmapped (or whose position
is -1) land there with stored position -1, so they are never attended to.
Masking semantics are identical to the dense cache — stored positions are the
single source of truth — which is what makes paged and dense decoding
token-identical (``tests/test_session.py``).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm, apply_rope, dense, dense_init

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params


def attn_init(key, cfg: ModelConfig, *, cross: bool = False, dtype=jnp.float32) -> dict:
    """Attention projections. ``cross=True`` reads K/V from memory_dim."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    kv_src = cfg.memory_dim if (cross and cfg.memory_dim) else d
    n_kv = cfg.n_heads if cross else cfg.n_kv_heads  # cross-attn: MHA over memory
    p = {
        "wq": dense_init(kq, d, cfg.n_heads * hd, use_bias=cfg.use_bias, dtype=dtype),
        "wk": dense_init(kk, kv_src, n_kv * hd, use_bias=cfg.use_bias, dtype=dtype),
        "wv": dense_init(kv, kv_src, n_kv * hd, use_bias=cfg.use_bias, dtype=dtype),
        "wo": dense_init(ko, cfg.n_heads * hd, d, use_bias=cfg.use_bias, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dtype)}
    return p


# ---------------------------------------------------------------------------
# cache


@dataclasses.dataclass
class KVCache:
    k: jnp.ndarray    # (B, S, n_kv, head_dim)
    v: jnp.ndarray    # (B, S, n_kv, head_dim)
    pos: jnp.ndarray  # (B, S) int32, absolute position stored in slot, -1 empty


jax.tree_util.register_dataclass(KVCache, data_fields=["k", "v", "pos"], meta_fields=[])


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *, cross: bool = False,
                  dtype=jnp.float32) -> KVCache:
    n_kv = cfg.n_heads if cross else cfg.n_kv_heads
    size = max_len if (cfg.sliding_window == 0 or cross) else min(max_len, cfg.sliding_window)
    return KVCache(
        k=jnp.zeros((batch, size, n_kv, cfg.head_dim), dtype),
        v=jnp.zeros((batch, size, n_kv, cfg.head_dim), dtype),
        pos=jnp.full((batch, size), -1, jnp.int32),
    )


def _write_cache(cache: KVCache, k_new, v_new, positions) -> KVCache:
    """Scatter new K/V at ``slot = position % S``; positions: (B, T)."""
    S = cache.k.shape[1]
    b_idx = jnp.arange(cache.k.shape[0])[:, None]
    slots = positions % S
    return KVCache(
        k=cache.k.at[b_idx, slots].set(k_new.astype(cache.k.dtype)),
        v=cache.v.at[b_idx, slots].set(v_new.astype(cache.v.dtype)),
        pos=cache.pos.at[b_idx, slots].set(positions.astype(jnp.int32)),
    )


# ---------------------------------------------------------------------------
# paged cache


TRASH_PAGE = 0  # reserved: writes with no mapped target land here, pos = -1

# Opt-in fast read path: the Pallas block-table kernel replaces paged_view's
# materialized (B, n_blocks*ps, ...) gather in cached_attention. Off by
# default — the XLA view is the reference. The flag is read at trace time,
# so flipping it after a function has been jitted means a retrace, not a
# silent no-op; flip it before warmup.
_PAGED_KERNEL = os.environ.get("REPRO_PAGED_KERNEL", "") not in ("", "0", "false")


def use_paged_kernel(enabled: bool = True) -> None:
    global _PAGED_KERNEL
    _PAGED_KERNEL = bool(enabled)


def paged_kernel_enabled() -> bool:
    return _PAGED_KERNEL


@dataclasses.dataclass
class PagedKVCache:
    """Block-table KV cache: a global page pool shared by all batch rows.

    ``block_tables[b, j]`` maps logical block ``j`` of row ``b`` to a page in
    the pool (-1 = unmapped). Logical position ``p`` of row ``b`` lives at
    ``(page=block_tables[b, (p // ps) % n_blocks], slot=p % ps)``. The pool
    (and stored positions) carry no batch axis, so batch-row ops — beam
    reorder, winner sync, slot recycling — touch ONLY the tiny block tables;
    page contents are shared by aliasing. The host allocator keeps the
    invariant that pages overlapping a row's write window ``[pos, pos+DL]``
    are privately owned (copy-on-write at the draft boundary).

    Cross-request prefix sharing (``repro.core.session.RadixPageCache``)
    adds one more aliasing form: a committed PROMPT page may be referenced
    by rows of SEVERAL requests, plus one reserved index-row cell that
    keeps it allocated after every owner leaves. The invariants that make
    this safe:

      - shared pages are read-only by construction — a decode write window
        starts at the prompt's final token, strictly above every fully
        committed prompt block, and prefix matches are truncated to full
        pages, so no lane ever writes into an aliased prefix page;
      - both page planners (the host walk and the on-device plan) elect a
        page's writer as its copy-on-write *keeper* only when that row
        holds the page's ONLY references — an extra reference from another
        request's row or from a radix index cell forces the writer to copy
        first, never to mutate in place;
      - attention masks on STORED positions, so which physical page backs
        a block never affects output — aliased and privately-owned reads
        are bitwise identical.

    Storage: a page holds ``ps`` token rows of ``n_kv * head_dim`` values,
    the heads folded into one minor axis. At a head_dim below the 128-lane
    tile (mt-retro: 8 heads x 32) an unfolded pool would be padded to 128
    lanes, or kept with pages minor-most and relaid out for every scatter;
    folded, it is dense at any head_dim. Heads are split again only in the
    gathered per-row view (``paged_view``). Model caches stack one pool per
    layer, ``(R, P, ps, n_kv * head_dim)``, and decode steps write and read
    it at ``[layer, page, slot]`` in place (``cached_attention``).
    """

    k_pool: jnp.ndarray        # (P, ps, n_kv * head_dim)
    v_pool: jnp.ndarray        # (P, ps, n_kv * head_dim)
    pos: jnp.ndarray           # (P, ps) int32, absolute position stored, -1 empty
    block_tables: jnp.ndarray  # (B, n_blocks) int32 page id, -1 unmapped

    @property
    def page_size(self) -> int:
        return self.pos.shape[-1]

    @property
    def n_blocks(self) -> int:
        return self.block_tables.shape[-1]


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=["k_pool", "v_pool", "pos", "block_tables"],
    meta_fields=[])


def init_paged_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                        n_pages: int, page_size: int, cross: bool = False,
                        dtype=jnp.float32) -> PagedKVCache:
    """Empty pool + unmapped tables. ``n_blocks`` covers the same logical
    length the dense cache would reserve per row (ring over blocks when a
    sliding window applies); page 0 is the reserved trash page."""
    n_kv = cfg.n_heads if cross else cfg.n_kv_heads
    size = max_len if (cfg.sliding_window == 0 or cross) else min(max_len, cfg.sliding_window)
    n_blocks = -(-size // page_size)
    if n_pages < 2:
        raise ValueError("n_pages must be >= 2 (page 0 is the trash page)")
    return PagedKVCache(
        k_pool=jnp.zeros((n_pages, page_size, n_kv * cfg.head_dim), dtype),
        v_pool=jnp.zeros((n_pages, page_size, n_kv * cfg.head_dim), dtype),
        pos=jnp.full((n_pages, page_size), -1, jnp.int32),
        block_tables=jnp.full((batch, n_blocks), -1, jnp.int32),
    )


def _lookup_pages(block_tables, positions, ps: int):
    """positions (B, T) -> (page (B, T), slot (B, T), mapped (B, T))."""
    nb = block_tables.shape[-1]
    blocks = (positions // ps) % nb
    b_idx = jnp.arange(block_tables.shape[0])[:, None]
    page = block_tables[b_idx, blocks]
    mapped = (page >= 0) & (positions >= 0)
    return jnp.where(mapped, page, TRASH_PAGE), positions % ps, mapped


def _write_cache_paged(cache: PagedKVCache, layer, k_new, v_new, positions
                       ) -> PagedKVCache:
    """Scatter new K/V (B, T, n_kv, hd) into layer ``layer`` of the stacked
    pool through the block table; positions: (B, T). One scatter per pool
    at ``[layer, page, slot]``, heads folded: the donated pool is updated
    in place. Invalid targets (position -1 or unmapped block) go to the
    trash page with stored position -1 — unreadable, exactly like the
    dense pad convention."""
    B, T = positions.shape
    page, slot, mapped = _lookup_pages(cache.block_tables[layer], positions,
                                       cache.page_size)
    store_pos = jnp.where(mapped, positions, -1).astype(jnp.int32)
    return dataclasses.replace(
        cache,
        k_pool=cache.k_pool.at[layer, page, slot].set(
            k_new.reshape(B, T, -1).astype(cache.k_pool.dtype)),
        v_pool=cache.v_pool.at[layer, page, slot].set(
            v_new.reshape(B, T, -1).astype(cache.v_pool.dtype)),
        pos=cache.pos.at[layer, page, slot].set(store_pos),
    )


def paged_view(cache: PagedKVCache, layer, n_kv: int):
    """Materialize layer ``layer``'s dense per-row view (k, v, kpos) the
    attention math expects: (B, n_blocks*ps, n_kv, hd) x2 + (B,
    n_blocks*ps) positions, one gather per pool at ``[layer, pages]``; the
    heads are split here. Unmapped blocks read the trash page but are
    masked to position -1. This is the XLA reference read path; the Pallas
    kernel (``repro.kernels.decode_gqa.paged_decode_gqa_attention``) walks
    the block table instead and never materializes the gather."""
    block_tables = cache.block_tables[layer]
    B, nb = block_tables.shape
    ps = cache.page_size
    pages = jnp.where(block_tables >= 0, block_tables, TRASH_PAGE)
    k = cache.k_pool[layer, pages].reshape(B, nb * ps, n_kv, -1)
    v = cache.v_pool[layer, pages].reshape(B, nb * ps, n_kv, -1)
    kpos = jnp.where(block_tables[..., None] >= 0, cache.pos[layer, pages],
                     -1)
    return k, v, kpos.reshape(B, nb * ps)


# ---------------------------------------------------------------------------
# core score/combine


def _gqa_attend(q, k, v, mask, *, q_per_kv: int):
    """q: (B,T,Hq,hd); k,v: (B,S,Kv,hd); mask: broadcastable (B,1,1,T,S)."""
    B, T, Hq, hd = q.shape
    Kv = k.shape[2]
    q = q.reshape(B, T, Kv, q_per_kv, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = jnp.where(mask, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w.astype(v.dtype), v)
    return out.reshape(B, T, Hq, hd)


def _project_qkv(p: dict, cfg: ModelConfig, x, kv_input, *, cross: bool):
    B, T = x.shape[:2]
    hd = cfg.head_dim
    n_kv = cfg.n_heads if cross else cfg.n_kv_heads
    q = dense(p["wq"], x).reshape(B, T, cfg.n_heads, hd)
    k = dense(p["wk"], kv_input).reshape(B, kv_input.shape[1], n_kv, hd)
    v = dense(p["wv"], kv_input).reshape(B, kv_input.shape[1], n_kv, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    return q, k, v


# ---------------------------------------------------------------------------
# modes


# q-chunk size above which exact blockwise attention kicks in: keeps the
# (B, H, Tq, S) score tensor off HBM for 32k prompts (flash-style memory
# behaviour at the XLA level; the Pallas kernel is the TPU fast path).
_Q_CHUNK = 1024


def _masked_attend(q, k, v, qp, kp_valid, kp, *, causal, window, q_per_kv):
    """Score q rows (positions qp) against keys (positions kp, validity
    kp_valid); lazy mask construction so callers can chunk the q axis."""
    B, Tq = q.shape[:2]
    mask = kp_valid[:, None, :]
    if causal:
        mask = mask & (kp[:, None, :] <= qp[:, :, None])
        if window > 0:
            mask = mask & (kp[:, None, :] > qp[:, :, None] - window)
    return _gqa_attend(q, k, v, mask[:, None, None], q_per_kv=q_per_kv)


def _attend_maybe_chunked(q, k, v, qp, kp_valid, kp, *, causal, window,
                          q_per_kv):
    """Exact attention; scans q chunks when Tq is long so the per-step score
    tensor is (B, H, chunk, S) instead of (B, H, Tq, S)."""
    B, Tq = q.shape[:2]
    if Tq <= _Q_CHUNK or Tq % _Q_CHUNK != 0:
        return _masked_attend(q, k, v, qp, kp_valid, kp, causal=causal,
                              window=window, q_per_kv=q_per_kv)
    n = Tq // _Q_CHUNK
    q_c = q.reshape(B, n, _Q_CHUNK, *q.shape[2:]).swapaxes(0, 1)
    qp_c = qp.reshape(B, n, _Q_CHUNK).swapaxes(0, 1)

    def body(_, inp):
        qi, qpi = inp
        out = _masked_attend(qi, k, v, qpi, kp_valid, kp, causal=causal,
                             window=window, q_per_kv=q_per_kv)
        return None, out

    _, outs = jax.lax.scan(body, None, (q_c, qp_c))
    return outs.swapaxes(0, 1).reshape(B, Tq, *q.shape[2:])


def attention(p: dict, cfg: ModelConfig, x, *, positions=None, causal: bool = True,
              padding_mask=None) -> jnp.ndarray:
    """Full-sequence self-attention (training / prefill, no cache).

    x: (B, T, d); positions: (B, T) absolute; padding_mask: (B, T) True=valid.
    Sliding window applies when cfg.sliding_window > 0 and causal.
    """
    B, T = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    q, k, v = _project_qkv(p, cfg, x, x, cross=False)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kp_valid = (jnp.ones((B, T), bool) if padding_mask is None
                else padding_mask)
    out = _attend_maybe_chunked(q, k, v, positions, kp_valid, positions,
                                causal=causal, window=cfg.sliding_window,
                                q_per_kv=cfg.q_per_kv)
    return dense(p["wo"], out.reshape(B, T, -1))


def cross_attention(p: dict, cfg: ModelConfig, x, memory, *, memory_mask=None) -> jnp.ndarray:
    """x: (B, T, d) queries; memory: (B, M, memory_dim or d)."""
    B, T = x.shape[:2]
    q, k, v = _project_qkv(p, cfg, x, memory, cross=True)
    mask = jnp.ones((B, T, memory.shape[1]), bool)
    if memory_mask is not None:
        mask &= memory_mask[:, None, :]
    out = _gqa_attend(q, k, v, mask[:, None, None], q_per_kv=1)
    return dense(p["wo"], out.reshape(B, T, -1))


def memory_kv(p: dict, cfg: ModelConfig, memory) -> dict:
    """Precompute cross-attention K/V from frontend memory (prefill-time)."""
    B, M = memory.shape[:2]
    hd = cfg.head_dim
    k = dense(p["wk"], memory).reshape(B, M, cfg.n_heads, hd)
    v = dense(p["wv"], memory).reshape(B, M, cfg.n_heads, hd)
    if cfg.qk_norm:
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    return {"mk": k, "mv": v}


def cached_cross_attention(p: dict, cfg: ModelConfig, x, cache: dict,
                           *, memory_mask=None) -> jnp.ndarray:
    """Cross-attention against precomputed memory K/V (decode-time)."""
    B, T = x.shape[:2]
    hd = cfg.head_dim
    q = dense(p["wq"], x).reshape(B, T, cfg.n_heads, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
    mask = jnp.ones((B, T, cache["mk"].shape[1]), bool)
    if memory_mask is not None:
        mask &= memory_mask[:, None, :]
    out = _gqa_attend(q, cache["mk"], cache["mv"], mask[:, None, None], q_per_kv=1)
    return dense(p["wo"], out.reshape(B, T, -1))


def multidraft_attention(p: dict, cfg: ModelConfig, x, cache: KVCache,
                         positions, local_mask):
    """Single-pass multi-draft verification attention (beyond-paper;
    DESIGN.md §2 / EXPERIMENTS.md §Perf).

    The paper verifies N_d drafts by inflating the batch to B·N_d — every
    draft row re-reads the whole KV cache. Here ONE row per sequence feeds
    all drafts: x = (B, T_local, d) with T_local = 1 + N_d·DL (last committed
    token + the drafts back-to-back); ``local_mask`` (T_local, T_local) is
    the static segment mask (token (j,i) sees token 0 and its own draft's
    prefix). Fed tokens attend jointly (one softmax) over:
      - the committed cache (READ ONCE per sequence — the N_d× saving), and
      - the local K/V of their own segment.
    Nothing is written to the cache; the caller commits the winning draft's
    accepted K/V afterwards (transformer.commit_verified).

    Returns (out (B, T_local, d), (k_new, v_new)) — local K/V for commit.
    """
    B, T = x.shape[:2]
    q, k_new, v_new = _project_qkv(p, cfg, x, x, cross=False)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    # cache part: committed entries only (invariant: cache holds committed
    # tokens < min(positions); no stale slots in the multidraft flow)
    kp = cache.pos[:, None, :]
    qp = positions[:, :, None]
    cache_mask = (kp >= 0) & (kp <= qp)
    if cfg.sliding_window > 0:
        cache_mask &= kp > qp - cfg.sliding_window
    # Two-part joint softmax (NO concatenation: concatenating (S + T_local)
    # keys copies the cache every layer and breaks its sequence sharding —
    # GSPMD then all-gathers the whole cache per layer; observed 126× worse
    # collective term before this formulation).
    Kv = cache.k.shape[2]
    G = cfg.q_per_kv
    hd = cfg.head_dim
    qh = q.reshape(B, T, Kv, G, hd)
    scale = 1.0 / math.sqrt(hd)
    s_c = jnp.einsum("btkgh,bskh->bkgts", qh, cache.k).astype(jnp.float32) * scale
    s_l = jnp.einsum("btkgh,bskh->bkgts", qh, k_new).astype(jnp.float32) * scale
    s_c = jnp.where(cache_mask[:, None, None], s_c, _NEG_INF)
    s_l = jnp.where(local_mask[None, None, None], s_l, _NEG_INF)
    m = jnp.maximum(jnp.max(s_c, axis=-1, keepdims=True),
                    jnp.max(s_l, axis=-1, keepdims=True))
    p_c = jnp.exp(s_c - m)
    p_l = jnp.exp(s_l - m)
    denom = (jnp.sum(p_c, axis=-1, keepdims=True)
             + jnp.sum(p_l, axis=-1, keepdims=True))
    p_c = (p_c / denom).astype(cache.v.dtype)
    p_l = (p_l / denom).astype(v_new.dtype)
    out = (jnp.einsum("bkgts,bskh->btkgh", p_c, cache.v)
           + jnp.einsum("bkgts,bskh->btkgh", p_l, v_new)).reshape(B, T, -1)
    return dense(p["wo"], out), (k_new, v_new)


def commit_verified_kv(cache: KVCache, k_new, v_new, take_idx, positions,
                       n_keep) -> KVCache:
    """Write the winning draft's accepted K/V into the cache.

    take_idx: (B, W) local indices of [last_tok, winning draft tokens];
    positions: (B, W) their absolute positions; n_keep: (B,) how many of the
    W are committed (the rest are written with stored position -1, i.e.
    invalid — their slots are re-written by the next commit before any
    query can see them)."""
    b = jnp.arange(take_idx.shape[0])[:, None]
    k_sel = k_new[b, take_idx]
    v_sel = v_new[b, take_idx]
    W = take_idx.shape[1]
    valid = jnp.arange(W)[None, :] < n_keep[:, None]
    S = cache.k.shape[1]
    slots = positions % S  # slot from the position; stored pos marks validity
    return KVCache(
        k=cache.k.at[b, slots].set(k_sel.astype(cache.k.dtype)),
        v=cache.v.at[b, slots].set(v_sel.astype(cache.v.dtype)),
        pos=cache.pos.at[b, slots].set(
            jnp.where(valid, positions, -1).astype(jnp.int32)),
    )


def cached_attention(p: dict, cfg: ModelConfig, x, cache, positions, *,
                     layer=None) -> tuple[jnp.ndarray, Any]:
    """Cached causal decode (and prefill-into-cache), dense or paged.

    x: (B, T, d) new tokens; positions: (B, T) absolute positions of those
    tokens (rows may differ — the speculative decoder relies on this).
    Pad-token convention: ``positions == -1`` marks invalid tokens; their K/V
    land in a throwaway slot with stored position -1, which every query masks.
    ``cache`` is one layer's ``KVCache``, or a ``PagedKVCache`` stacked over
    the model's layers with ``layer`` (an int32 scalar, may be traced)
    naming this layer: the paged pool is written and read at that layer in
    place (the XLA read path never slices it out). Masking semantics are
    identical, so the two produce the same output for the same stored
    tokens. Returns output (B, T, d) and the updated cache.
    """
    B, T = x.shape[:2]
    q, k_new, v_new = _project_qkv(p, cfg, x, x, cross=False)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    if isinstance(cache, PagedKVCache):
        cache = _write_cache_paged(cache, layer, k_new, v_new, positions)
        if _PAGED_KERNEL:
            # Lazy import: models must not depend on the kernels package
            # unless the fast path is actually enabled.
            from repro.kernels.decode_gqa import paged_decode_gqa_attention
            out = paged_decode_gqa_attention(
                q, cache.k_pool[layer], cache.v_pool[layer], cache.pos[layer],
                cache.block_tables[layer], positions,
                window=cfg.sliding_window)
            return dense(p["wo"], out.reshape(B, T, -1)), cache
        with jax.named_scope("page_view"):
            k, v, kpos = paged_view(cache, layer, k_new.shape[2])
    else:
        cache = _write_cache(cache, k_new, v_new, positions)
        k, v, kpos = cache.k, cache.v, cache.pos
    out = _attend_maybe_chunked(
        q, k, v, positions, kpos >= 0, kpos,
        causal=True, window=cfg.sliding_window, q_per_kv=cfg.q_per_kv)
    return dense(p["wo"], out.reshape(B, T, -1)), cache
