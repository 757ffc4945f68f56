"""Plain reference of the Molecular Transformer as the program serves it.

Encoder-decoder, pre-LN residual blocks, LayerNorm (eps 1e-6), sinusoidal
positions over 512 places, token embedding scaled by sqrt(d_model), MHA
with biases, GELU (tanh form) feed-forward, untied output head. Schwaller
et al. 2019 use post-LN and ReLU; the program departs from them in those
two places and this reference follows the program, since the benchmark
judges the program's arithmetic, not its choice of architecture.

Straight ``jax.numpy``: no cache, no batching tricks, no kernels. The
parameter tree has the program's layout (``enc_blocks``/``dec_blocks``
stacked over layers), so one tree feeds both. ``dtype`` casts the
parameters and every activation (the bfloat16 control); the caller sets
the matmul precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30
MAX_POS = 512
LN_EPS = 1e-6


def _dense_init(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out)) / math.sqrt(d_in),
            "b": jnp.zeros((d_out,))}


def _norm_init(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def _attn_init(key, d):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"wq": _dense_init(kq, d, d), "wk": _dense_init(kk, d, d),
            "wv": _dense_init(kv, d, d), "wo": _dense_init(ko, d, d)}


def _ffn_init(key, d, d_ff):
    k1, k2 = jax.random.split(key)
    return {"w_in": _dense_init(k1, d, d_ff), "w_out": _dense_init(k2, d_ff, d)}


def init_params(key, cfg: dict) -> dict:
    """Random f32 parameters in the program's tree layout."""
    d, d_ff, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    k_emb, k_enc, k_dec, k_out = jax.random.split(key, 4)

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": _norm_init(d), "attn": _attn_init(k1, d),
                "norm2": _norm_init(d), "ffn": _ffn_init(k2, d, d_ff)}

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"norm1": _norm_init(d), "self_attn": _attn_init(k1, d),
                "norm_x": _norm_init(d), "cross_attn": _attn_init(k2, d),
                "norm2": _norm_init(d), "ffn": _ffn_init(k3, d, d_ff)}

    return {
        "tok": {"embed": jax.random.normal(k_emb, (V, d)) * 0.02},
        "enc_blocks": jax.vmap(enc_block)(
            jax.random.split(k_enc, cfg["n_encoder_layers"])),
        "enc_norm": _norm_init(d),
        "dec_blocks": jax.vmap(dec_block)(
            jax.random.split(k_dec, cfg["n_decoder_layers"])),
        "dec_norm": _norm_init(d),
        "lm_head": {"w_vocab": jax.random.normal(k_out, (d, V)) / math.sqrt(d)},
    }


def positions_table(d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(MAX_POS, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10_000.0) / d))
    pe = jnp.zeros((MAX_POS, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


def layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def linear(p, x):
    return x @ p["w"] + p["b"]


def mha(p, x, kv, mask, n_heads):
    """x: (B, T, d) queries; kv: (B, S, d); mask: (B, T, S) True = attend."""
    B, T, d = x.shape
    S, hd = kv.shape[1], d // n_heads
    q = linear(p["wq"], x).reshape(B, T, n_heads, hd)
    k = linear(p["wk"], kv).reshape(B, S, n_heads, hd)
    v = linear(p["wv"], kv).reshape(B, S, n_heads, hd)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
    s = jnp.where(mask[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", w, v).reshape(B, T, d)
    return linear(p["wo"], o)


def _embed(params, tokens, d, dtype):
    x = params["tok"]["embed"][tokens] * math.sqrt(d)
    return x + positions_table(d, dtype)[:tokens.shape[1]]


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)


def encode(params, cfg: dict, src, dtype=jnp.float32):
    """src: (B, S) ids, 0 = pad. Returns (memory (B, S, d), mask (B, S))."""
    d, H = cfg["d_model"], cfg["n_heads"]
    params = cast(params, dtype)
    valid = src != 0
    mask = jnp.broadcast_to(valid[:, None, :],
                            (src.shape[0], src.shape[1], src.shape[1]))
    x = _embed(params, src, d, dtype)
    for i in range(cfg["n_encoder_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"])
        h = layer_norm(p["norm1"], x)
        x = x + mha(p["attn"], h, h, mask, H)
        x = x + linear(p["ffn"]["w_out"],
                       gelu(linear(p["ffn"]["w_in"], layer_norm(p["norm2"], x))))
    return layer_norm(params["enc_norm"], x), valid


def decode(params, cfg: dict, tgt_in, memory, src_valid, dtype=jnp.float32):
    """Teacher-forced decoder: tgt_in (B, T) ids starting with BOS. Returns
    logits (B, T, V) in ``dtype``."""
    d, H = cfg["d_model"], cfg["n_heads"]
    params = cast(params, dtype)
    B, T = tgt_in.shape
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    cross = jnp.broadcast_to(src_valid[:, None, :],
                             (B, T, src_valid.shape[1]))
    x = _embed(params, tgt_in, d, dtype)
    for i in range(cfg["n_decoder_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["dec_blocks"])
        h = layer_norm(p["norm1"], x)
        x = x + mha(p["self_attn"], h, h, causal, H)
        x = x + mha(p["cross_attn"], layer_norm(p["norm_x"], x), memory,
                    cross, H)
        x = x + linear(p["ffn"]["w_out"],
                       gelu(linear(p["ffn"]["w_in"], layer_norm(p["norm2"], x))))
    return layer_norm(params["dec_norm"], x) @ params["lm_head"]["w_vocab"]


def forward(params, cfg: dict, src, tgt_in, dtype=jnp.float32):
    memory, valid = encode(params, cfg, src, dtype)
    return decode(params, cfg, tgt_in, memory, valid, dtype)
