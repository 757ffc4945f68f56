"""Operations of the plain algorithm, from shapes, and the peak table.

A query's FLOPs count the encoder over its source, the cross-attention
K/V of its memory (once per query), and the decoder over the committed
output of each of its K candidates (K = 1 greedy, 5 beam), attention
terms included. A multiply-add is 2 FLOPs. Verification of rejected
draft positions is work the plain algorithm does not do and is not
counted; nor is the bookkeeping of the page table or of beam reorders.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def encoder(cfg: dict, S: int) -> float:
    """One source of ``S`` tokens through the encoder."""
    d, f = cfg["d_model"], cfg["d_ff"]
    per_layer = (4 * 2 * S * d * d        # q, k, v, o projections
                 + 2 * 2 * S * S * d      # scores and weighted sum
                 + 2 * 2 * S * d * f)     # feed-forward in and out
    return float(cfg["n_encoder_layers"] * per_layer)


def cross_kv(cfg: dict, S: int) -> float:
    """Cross-attention K and V of the memory, every decoder layer."""
    d = cfg["d_model"]
    return float(cfg["n_decoder_layers"] * 2 * 2 * S * d * d)


def decoder(cfg: dict, S: int, L: int) -> float:
    """One candidate of ``L`` committed tokens, positions 0..L-1."""
    d, f, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    # sum over positions p of the self-attention span p + 1
    span = L * (L + 1) / 2
    per_layer = (L * (4 * 2 * d * d            # self q, k, v, o
                      + 2 * 2 * d * d          # cross q, o
                      + 2 * 2 * S * d          # cross scores + sum
                      + 2 * 2 * d * f)         # feed-forward
                 + 2 * 2 * span * d)           # self scores + sum
    return float(cfg["n_decoder_layers"] * per_layer + L * 2 * d * V)


def query(cfg: dict, S: int, lengths) -> float:
    """All FLOPs of one query: source length ``S`` (with EOS) and the
    committed lengths of its candidates."""
    return (encoder(cfg, S) + cross_kv(cfg, S)
            + sum(decoder(cfg, S, int(L)) for L in lengths))


def peak(device_kind: str) -> dict:
    """The chip's peaks; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]
