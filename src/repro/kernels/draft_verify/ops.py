"""jit-able wrapper: pads vocab to tile multiples, reshapes mask."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.draft_verify.kernel import draft_verify_kernel
from repro.kernels.platform import interpret_mode


@partial(jax.jit, static_argnames=("bv", "interpret"))
def draft_verify(logits, drafts, draft_mask, *, bv: int = 512,
                 interpret: bool | None = None):
    """logits: (N, T, V); drafts: (N, T-1) int32; draft_mask: (N,) bool.

    Returns (greedy_tokens (N, T) int32, n_acc (N,) int32) — the fused
    equivalent of argmax + ``core.speculative._accept_lengths``.
    """
    N, T, V = logits.shape
    bv = min(bv, max(128, V))
    Vp = ((V + bv - 1) // bv) * bv
    if Vp != V:
        logits = jnp.pad(logits, ((0, 0), (0, 0), (0, Vp - V)),
                         constant_values=-1e30)
    mask_i = draft_mask.astype(jnp.int32)[:, None]
    toks, acc = draft_verify_kernel(logits, drafts, mask_i, bv=bv,
                                    interpret=interpret_mode(interpret))
    return toks, acc[:, 0]
