"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the window finished (drawn from the seed, the longest always in
it) is run through the plain reference (``bench.model``) in float32 at
"highest" matmul precision, teacher-forced on each served candidate.
Numbers compared:

``token_gap``       greedy family: the widest gap, in logits, by which a
                    served token lies below the reference's best token at
                    its position. Speculative tokens are accepted only
                    where they equal the model's own argmax, so a sound
                    run reads rounding near ties.
``token_gap_top5``  beam family: the widest gap by which a served token
                    lies below the reference's 5th best (pad excluded, as
                    the program excludes it). Every beam token is either
                    an accepted draft token (the argmax) or one of the
                    top 5 extensions of its parent.
``unfinished``      requests the window owed an answer and never gave
                    one (limit 0).

The control (``calibrate.py control``, ``tests/test_control.py``) puts the
reference in bfloat16 in the program's place and reads the same gaps for
the tokens bfloat16 ranks first at each served position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import chem, model

SAMPLE = 48      # requests compared per run
BLOCK = 8        # requests per reference dispatch


def sample(finished: list, seed: int, n: int = SAMPLE) -> list:
    """``finished``: [(src ids, result)]. Up to ``n`` of them, drawn from
    the seed, with the one of longest top candidate always included."""
    if len(finished) <= n:
        return list(finished)
    longest = int(np.argmax([int(r.lengths[0]) for _, r in finished]))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = np.random.default_rng(seed).choice(len(rest), n - 1, replace=False)
    return [finished[longest]] + [finished[rest[i]] for i in sorted(pick)]


@functools.partial(jax.jit, static_argnums=(1, 4))
def _block_logits(params, cfg_items, src, tgt_in, dtype):
    cfg = dict(cfg_items)
    b, K, T = tgt_in.shape
    memory, valid = model.encode(params, cfg, src, dtype)
    memory = jnp.repeat(memory, K, axis=0)
    valid = jnp.repeat(valid, K, axis=0)
    logits = model.decode(params, cfg, tgt_in.reshape(b * K, T), memory,
                          valid, dtype)
    return logits.astype(jnp.float32).reshape(b, K, T, -1)


def cfg_items(cfg: dict) -> tuple:
    keys = ("d_model", "n_heads", "d_ff", "vocab_size", "n_encoder_layers",
            "n_decoder_layers")
    return tuple((k, int(cfg[k])) for k in keys)


def teacher_inputs(tokens: np.ndarray) -> np.ndarray:
    """(n, K, T) served tokens -> decoder inputs [BOS] + tokens[:-1]."""
    bos = np.full(tokens.shape[:-1] + (1,), chem.BOS, np.int32)
    return np.concatenate([bos, tokens[..., :-1]], axis=-1).astype(np.int32)


def logits(params, cfg: dict, src: np.ndarray, tokens: np.ndarray,
           dtype=jnp.float32, precision: str = "highest") -> np.ndarray:
    """Reference logits (n, K, T, V) at every served position, computed
    ``BLOCK`` requests per dispatch so that it fits beside anything."""
    src, n = _blocks(src)
    tin, _ = _blocks(teacher_inputs(tokens))
    out = []
    with jax.default_matmul_precision(precision):
        for i in range(0, src.shape[0], BLOCK):
            out.append(np.asarray(_block_logits(
                params, cfg_items(cfg), jnp.asarray(src[i:i + BLOCK]),
                jnp.asarray(tin[i:i + BLOCK]), dtype)))
    return np.concatenate(out)[:n]


def _blocks(src: np.ndarray):
    """``src`` padded to whole ``BLOCK``s of rows, and the true count."""
    n = src.shape[0]
    pad = (-n) % BLOCK
    return np.concatenate([src, np.repeat(src[:1], pad, 0)]), n


def _valid(lengths: np.ndarray, T: int) -> np.ndarray:
    return np.arange(T)[None, None, :] < lengths[..., None]


def _kth(lg: np.ndarray, k: int, exclude_pad: bool) -> np.ndarray:
    if exclude_pad:
        lg = lg.copy()
        lg[..., chem.PAD] = -np.inf
    return np.sort(lg, axis=-1)[..., -k]


def token_gap(ref: np.ndarray, tokens: np.ndarray, lengths: np.ndarray,
              k: int, exclude_pad: bool) -> float:
    """Widest amount by which a served token's reference logit lies below
    the reference's k-th best at its position (0 inside the top k)."""
    tok = np.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    gap = np.maximum(_kth(ref, k, exclude_pad) - tok, 0.0)
    return float(np.max(np.where(_valid(lengths, ref.shape[2]), gap, 0.0)))


def control_token_gap(ref, low, lengths, k, exclude_pad) -> float:
    """The control's reading: at each position, the gap under the
    reference of each of the k tokens the lower precision ranks first."""
    lw = low.copy()
    if exclude_pad:
        lw[..., chem.PAD] = -np.inf
    top = np.argsort(-lw, axis=-1, kind="stable")[..., :k]
    vals = np.take_along_axis(ref, top, -1)
    gap = np.maximum(_kth(ref, k, exclude_pad)[..., None] - vals, 0.0).max(-1)
    return float(np.max(np.where(_valid(lengths, ref.shape[2]), gap, 0.0)))


def stack(picked: list, max_new: int):
    """[(src, result)] -> src (n, S), tokens (n, K, T), lengths (n, K)."""
    src = np.stack([np.asarray(s, np.int32) for s, _ in picked])
    toks = np.stack([np.asarray(r.tokens, np.int32)[:, :max_new]
                     for _, r in picked])
    lens = np.stack([np.asarray(r.lengths, np.int32) for _, r in picked])
    return src, toks, lens


def numbers(params, cfg: dict, picked: list, beam: bool, max_new: int,
            control: bool = False) -> dict:
    """The compared numbers of the sample. ``control``: also the bfloat16
    control's readings, under ``control.<name>``."""
    src, toks, lens = stack(picked, max_new)
    ref = logits(params, cfg, src, toks)
    k, name = (5, "token_gap_top5") if beam else (1, "token_gap")
    out = {name: token_gap(ref, toks, lens, k, beam)}
    if control:
        low = logits(params, cfg, src, toks, dtype=jnp.bfloat16,
                     precision="default")
        out["control." + name] = control_token_gap(ref, low, lens, k, beam)
    return out
