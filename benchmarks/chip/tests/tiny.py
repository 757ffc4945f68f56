"""A tiny cell for CPU runs of the harness: the real harness, a model
small enough for the CPU."""

from __future__ import annotations

import copy

from bench import cells

CONFIG = {
    "name": "mt-tiny", "direction": "forward",
    "n_encoder_layers": 2, "n_decoder_layers": 2, "d_model": 64,
    "n_heads": 4, "d_ff": 256, "vocab_size": 28, "max_positions": 512,
    "engine": {"n_slots": {"speculative": 2, "speculative_beam": 2, "greedy": 2},
                "n_beams": 3, "n_drafts": 4, "draft_len": 4,
               "max_new": 64, "max_src": 96, "page_size": 16},
    "train": {"steps": 200, "chunk": 100, "batch": 32, "lr": 0.002,
              "warmup": 40, "corpus": 1024, "src_len": 96, "tgt_len": 64},
}


def cell(mode: str = "speculative", load: str = "backlog",
         limits: dict | None = None, **mix) -> cells.Cell:
    traffic = {"mode": mode, "load": load, "backlog_slots": 1,
               "drain_s": 30, "pool": {"size": 256, "seed": 3}, **mix}
    beam = mode in ("beam", "speculative_beam")
    if limits is None:
        limits = {"token_gap_top5" if beam else "token_gap": 0.05,
                  "unfinished": 0}
    return cells.Cell(
        name=f"mt-tiny.{mode}.{load}", workload={"chips": 1},
        config=copy.deepcopy(CONFIG), config_entry={"name": "mt-tiny"},
        traffic=traffic, limits=limits, metrics=[])
