"""Encoder-decoder transformer — the Molecular Transformer (Schwaller 2019).

SMILES-to-SMILES translation: encoder over reactant tokens, autoregressive
decoder with cross-attention over the encoder memory. This is the model the
paper accelerates; its decoder exposes the same ``decode_step`` contract as
``repro.models.transformer`` so the speculative decoders in ``repro.core``
work on both.

Deviations from the 2019 OpenNMT implementation (recorded per DESIGN.md §2):
pre-LN residual blocks instead of post-LN (training stability; accuracy
parity is re-validated against our own beam-search baseline, which is what
the paper itself does in Table 1), GELU instead of ReLU.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.attention import attention, cached_attention, cross_attention
from repro.models.layers import (
    apply_norm, dense, embed, embed_init, ffn, ffn_init, logits_init, norm_init,
    sinusoidal_positions,
)


# ---------------------------------------------------------------------------
# init


def _enc_block_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": attn_mod.attn_init(k1, cfg, dtype=dtype),
        "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
        "ffn": ffn_init(k2, cfg.d_model, cfg.d_ff, use_bias=cfg.use_bias,
                        gated=cfg.gated_ffn, dtype=dtype),
    }


def _dec_block_init(key, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": norm_init(cfg.d_model, cfg.norm, dtype),
        "self_attn": attn_mod.attn_init(k1, cfg, dtype=dtype),
        "norm_x": norm_init(cfg.d_model, cfg.norm, dtype),
        "cross_attn": attn_mod.attn_init(k2, cfg, cross=True, dtype=dtype),
        "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
        "ffn": ffn_init(k3, cfg.d_model, cfg.d_ff, use_bias=cfg.use_bias,
                        gated=cfg.gated_ffn, dtype=dtype),
    }


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    assert cfg.family == "seq2seq" and cfg.n_encoder_layers > 0
    k_emb, k_enc, k_dec, k_out = jax.random.split(key, 4)
    enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return {
        "tok": embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),  # shared
        "enc_blocks": jax.vmap(partial(_enc_block_init, cfg=cfg, dtype=dtype))(enc_keys),
        "enc_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "dec_blocks": jax.vmap(partial(_dec_block_init, cfg=cfg, dtype=dtype))(dec_keys),
        "dec_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "lm_head": logits_init(k_out, cfg.d_model, cfg.vocab_size, dtype),
    }


def _embed_pos(params, cfg: ModelConfig, tokens, positions):
    x = embed(params["tok"], tokens) * math.sqrt(cfg.d_model)
    pe = sinusoidal_positions(cfg.max_len, cfg.d_model, x.dtype)
    return x + pe[jnp.clip(positions, 0)]


# ---------------------------------------------------------------------------
# encoder


def encode(params, cfg: ModelConfig, src, *, src_mask=None):
    """src: (B, S) token ids; src_mask: (B, S) True=valid (default: != 0/pad).

    Returns (memory (B, S, d), src_mask).
    """
    if src_mask is None:
        src_mask = src != 0
    B, S = src.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _embed_pos(params, cfg, src, positions)

    def body(h, p):
        a = attention(p["attn"], cfg, apply_norm(p["norm1"], h, cfg.norm),
                      positions=positions, causal=False, padding_mask=src_mask)
        h = h + a
        f = ffn(p["ffn"], apply_norm(p["norm2"], h, cfg.norm))
        return h + f, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return apply_norm(params["enc_norm"], x, cfg.norm), src_mask


# ---------------------------------------------------------------------------
# decoder (full sequence — training)


def decode(params, cfg: ModelConfig, tgt_in, memory, src_mask, *, lengths=None):
    """Teacher-forced decoder pass. tgt_in: (B, T). Returns logits (B, T, V)."""
    B, T = tgt_in.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = _embed_pos(params, cfg, tgt_in, positions)
    pad_mask = None if lengths is None else (jnp.arange(T) < lengths[:, None])

    def body(h, p):
        a = attention(p["self_attn"], cfg, apply_norm(p["norm1"], h, cfg.norm),
                      positions=positions, causal=True, padding_mask=pad_mask)
        h = h + a
        c = cross_attention(p["cross_attn"], cfg, apply_norm(p["norm_x"], h, cfg.norm),
                            memory, memory_mask=src_mask)
        h = h + c
        f = ffn(p["ffn"], apply_norm(p["norm2"], h, cfg.norm))
        return h + f, None

    x, _ = jax.lax.scan(body, x, params["dec_blocks"])
    x = apply_norm(params["dec_norm"], x, cfg.norm)
    return x @ params["lm_head"]["w_vocab"]


def apply(params, cfg: ModelConfig, src, tgt_in, *, src_mask=None, lengths=None):
    """Full training forward: returns (logits, aux={})."""
    memory, src_mask = encode(params, cfg, src, src_mask=src_mask)
    return decode(params, cfg, tgt_in, memory, src_mask, lengths=lengths), {}


# ---------------------------------------------------------------------------
# cached decode (serving) — contract shared with repro.models.transformer


def init_cache(cfg: ModelConfig, batch: int, max_len: int, memory=None,
               params=None, dtype=jnp.float32, memory_len=None,
               memory_mask=None, paged=None) -> dict:
    """Self-attn KV caches + precomputed cross K/V (if memory given).

    ``memory_len``: cross K/V width when ``memory`` is absent — the
    continuous-batching session allocates empty rows up front and scatters
    each request's memory K/V in at admission time.
    ``memory_mask``: (batch, M) True=valid; when given it is stored INSIDE
    the cache (leaf shape (1, batch, M), batch on axis 1 like every other
    leaf), so batch-row expansion/gather/scatter ops carry each row's mask
    along and ``decode_step`` needs no closed-over mask.
    ``paged``: ``(n_pages, page_size)`` — allocate the self-attn cache as a
    ``PagedKVCache`` (one pool per decoder layer, stacked to ``(n_layers,
    n_pages, page_size, n_kv_heads * head_dim)``) instead of dense rows; the
    caller owns page mapping (``repro.core.session.PageAllocator``). The
    cross K/V stays dense: it is fixed-size per request and written once at
    admission.
    """
    R = cfg.n_layers
    stack = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (R,) + a.shape), t)
    if paged is not None:
        n_pages, page_size = paged
        self_cache = stack(attn_mod.init_paged_kv_cache(
            cfg, batch, max_len, n_pages=n_pages, page_size=page_size,
            dtype=dtype))
    else:
        self_cache = stack(attn_mod.init_kv_cache(cfg, batch, max_len,
                                                  dtype=dtype))
    if memory is not None and params is not None:
        mkv = jax.vmap(
            lambda p: attn_mod.memory_kv(p, cfg, memory)
        )(params["dec_blocks"]["cross_attn"])
    else:
        M = (memory_len if memory_len is not None
             else (1 if memory is None else memory.shape[1]))
        mkv = stack({"mk": jnp.zeros((batch, M, cfg.n_heads, cfg.head_dim), dtype),
                     "mv": jnp.zeros((batch, M, cfg.n_heads, cfg.head_dim), dtype)})
    cache = {"self": self_cache, "cross": mkv}
    if memory_mask is not None:
        cache["mmask"] = jnp.asarray(memory_mask, bool)[None]
    return cache


def decode_step(params, cfg: ModelConfig, cache, tokens, positions, *,
                memory_mask=None):
    """Feed T new tokens (T = DL+1 for verification). Returns (logits, cache).

    ``positions``: (B, T) absolute target positions (rows may differ) — this
    is the JAX-native equivalent of the paper's padLeft + shifted positional
    encodings (DESIGN.md §2). When no explicit ``memory_mask`` is passed the
    per-row mask stored in the cache (if any) applies. Each layer's parts
    run under the named scopes ``self_attn``, ``cross_attn`` and ``ffn``,
    the output projection under ``lm_head``. A paged self-attention pool is
    threaded through the layer scan whole and updated in place; no layer's
    pool is copied out of the stack or back.
    """
    if memory_mask is None and "mmask" in cache:
        memory_mask = cache["mmask"][0]
    x = _embed_pos(params, cfg, tokens, positions)
    # A paged pool rides in the carry, written and read at [layer, ...] in
    # place; a dense cache is sliced per layer (scan xs/ys).
    paged = isinstance(cache["self"], attn_mod.PagedKVCache)
    pool = cache["self"] if paged else None
    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)

    def body(carry, xs):
        h, pool = carry
        p, c_self, c_cross, layer = xs
        with jax.named_scope("self_attn"):
            a, c_self = cached_attention(
                p["self_attn"], cfg, apply_norm(p["norm1"], h, cfg.norm),
                pool if paged else c_self, positions, layer=layer)
            h = h + a
        with jax.named_scope("cross_attn"):
            c = attn_mod.cached_cross_attention(
                p["cross_attn"], cfg, apply_norm(p["norm_x"], h, cfg.norm),
                c_cross, memory_mask=memory_mask)
            h = h + c
        with jax.named_scope("ffn"):
            h = h + ffn(p["ffn"], apply_norm(p["norm2"], h, cfg.norm))
        return ((h, c_self), None) if paged else ((h, None), c_self)

    dense_self = None if paged else cache["self"]
    (x, pool), new_self = jax.lax.scan(
        body, (x, pool),
        (params["dec_blocks"], dense_self, cache["cross"], layers))
    with jax.named_scope("lm_head"):
        x = apply_norm(params["dec_norm"], x, cfg.norm)
        logits = x @ params["lm_head"]["w_vocab"]
    new_cache = {"self": pool if paged else new_self, "cross": cache["cross"]}
    if "mmask" in cache:
        new_cache["mmask"] = cache["mmask"]
    return logits, new_cache


def commit_cache(cfg: ModelConfig, cache, n_keep):
    """KV caches need no rollback (stale slots are overwritten; see
    repro.models.transformer docstring)."""
    return cache
