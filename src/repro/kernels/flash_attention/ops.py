"""jit-able wrapper: (B, H, S, hd) API with padding to block multiples."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.platform import interpret_mode


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q,k,v: (B, H, S, hd) -> (B, H, S, hd). Pads S up to block multiples;
    padded key positions are masked inside the kernel via seq_len."""
    B, H, S, hd = q.shape
    bq = min(bq, max(8, S))
    bk = min(bk, max(8, S))
    Sp = ((S + max(bq, bk) - 1) // max(bq, bk)) * max(bq, bk)
    pad = Sp - S
    if pad:
        padder = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q, k, v = padder(q), padder(k), padder(v)
    qf = q.reshape(B * H, Sp, hd)
    kf = k.reshape(B * H, Sp, hd)
    vf = v.reshape(B * H, Sp, hd)
    # seq_len masking inside the kernel handles padded keys; padded queries
    # produce garbage rows that are sliced off below.
    out = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                                 bq=bq, bk=bk, seq_len=S,
                                 interpret=interpret_mode(interpret))
    return out.reshape(B, H, Sp, hd)[:, :, :S, :]
