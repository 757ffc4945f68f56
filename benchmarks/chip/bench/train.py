"""Weights trained on the device from the seed, as set-up of every run.

Random weights never emit EOS and accept no drafts, so a benchmark on them
measures neither real output lengths nor the paper's mechanism. This loop
trains the reference model (``bench.model``) on synthetic reactions:
cross-entropy on teacher-forced targets, Adam with linear warm-up and
cosine decay, a fixed step count and a data order drawn from the seed. It
runs as ``steps / chunk`` dispatches of one jitted ``lax.scan`` chunk, so
one program serves every chunk and the persistent compile cache holds it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import chem, model


def loss_fn(params, cfg, src, tgt):
    """Mean token cross-entropy; ``tgt`` (B, T) is EOS-terminated."""
    tgt_in = jnp.concatenate(
        [jnp.full((tgt.shape[0], 1), chem.BOS, tgt.dtype), tgt[:, :-1]], 1)
    logits = model.forward(params, cfg, src, tgt_in)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    mask = (tgt != chem.PAD).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lr_at(step, lr, warmup, total):
    s = step.astype(jnp.float32)
    ramp = jnp.minimum(1.0, (s + 1.0) / warmup)
    frac = jnp.clip((s - warmup) / jnp.maximum(1.0, total - warmup), 0.0, 1.0)
    decay = 0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(math.pi * frac))
    return lr * ramp * decay


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros}


def adam_update(params, grads, opt, step, lr, b1=0.9, b2=0.98, eps=1e-9):
    t = step.astype(jnp.float32) + 1.0
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               opt["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               opt["v"], grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return params, {"m": m, "v": v}


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _chunk(cfg_items, batch, chunk, params, opt, src, tgt, key, step0,
           sched):
    cfg = dict(cfg_items)
    B = batch

    def one(carry, i):
        params, opt = carry
        step = step0 + i
        idx = jax.random.randint(jax.random.fold_in(key, step), (B,), 0,
                                 src.shape[0])
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, src[idx],
                                                  tgt[idx])
        params, opt = adam_update(params, grads, opt, step,
                                  lr_at(step, *sched))
        return (params, opt), loss

    (params, opt), losses = jax.lax.scan(
        one, (params, opt), jnp.arange(chunk, dtype=jnp.int32))
    return params, opt, losses


@functools.partial(jax.jit, static_argnums=(0,))
def _init(cfg_items, key):
    params = model.init_params(key, dict(cfg_items))
    return params, adam_init(params)


def prng_key(seed: int):
    """A key from any whole seed (``--seed`` may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def corpus(cfg: dict, tcfg: dict, seed: int):
    """The seed's training pairs as device arrays (src, tgt)."""
    tok = chem.Tokenizer()
    prs = chem.pairs(tcfg["corpus"], seed, cfg["direction"])
    src, tgt = chem.encode_pairs(tok, prs, tcfg["src_len"], tcfg["tgt_len"])
    return jnp.asarray(src), jnp.asarray(tgt)


def train(cfg: dict, tcfg: dict, seed: int, steps: int | None = None):
    """Initialise from ``seed`` and train ``tcfg['steps']`` steps (or
    ``steps``). Returns ``(params, losses)``: f32 parameters on the
    default device and the per-step losses as a numpy array."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    k_init, k_data = jax.random.split(prng_key(seed))
    params, opt = _init(cfg_items, k_init)
    src, tgt = corpus(cfg, tcfg, seed)
    total = tcfg["steps"] if steps is None else steps
    sched = (jnp.float32(tcfg["lr"]), jnp.float32(tcfg["warmup"]),
             jnp.float32(total))
    losses = []
    for c in range(0, total, tcfg["chunk"]):
        params, opt, l = _chunk(cfg_items, tcfg["batch"], tcfg["chunk"],
                                params, opt, src, tgt, k_data, jnp.int32(c),
                                sched)
        losses.append(l)
    return params, np.concatenate([np.asarray(x) for x in losses])
