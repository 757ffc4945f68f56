"""The seq2seq family: encoder-decoder Molecular Transformers (Schwaller
et al. 2019, as configured in arXiv 2407.09685 Appendix A) on SMILES.

What ``run.py`` asks of a family, for a cell of it:

``model_cfg``      the reference's sizes, from the configuration file
``program_cfg``    the program's ``ModelConfig`` for the same sizes
``tokenizer``      the tokenizer the program's engine is built with
``train``          weights trained on the device from the seed
``pool``           the mix's query pool, one row of token ids per query
``query_flops``    plain-algorithm FLOPs of one finished query
``check_numbers``  the numbers compared with the plain reference

The reference, the training loop, the generator and the FLOP count are
the benchmark's own copies (``bench.model``, ``bench.train``,
``bench.chem``, ``bench.flops``); only ``program_cfg`` imports the
program.
"""

from __future__ import annotations

from bench import chem, check, flops, traffic
from bench import train as training


def model_cfg(cell) -> dict:
    c = cell.config
    return {k: c[k] for k in ("d_model", "n_heads", "d_ff", "vocab_size",
                              "n_encoder_layers", "n_decoder_layers",
                              "direction")}


def program_cfg(cell):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import ModelConfig

    c = cell.config
    return ModelConfig(
        name=cell.config_entry["name"], family="seq2seq",
        n_layers=c["n_decoder_layers"], n_encoder_layers=c["n_encoder_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"], n_kv_heads=c["n_heads"],
        d_ff=c["d_ff"], vocab_size=c["vocab_size"], use_bias=True,
        norm="layernorm", gated_ffn=False, pos="sinusoidal",
        max_len=c["max_positions"])


def tokenizer():
    return chem.Tokenizer()


def train(cell, seed: int, steps: int | None = None):
    """``(params, losses)`` trained from ``seed`` (``bench.train``) as the
    configuration's ``train`` block says; ``steps`` overrides its count."""
    return training.train(model_cfg(cell), cell.config["train"], seed,
                          steps=steps)


def pool(cell):
    """The mix's query pool: (P, max_src) EOS-terminated source ids."""
    e = cell.config["engine"]
    src, _ = traffic.pool(cell.config["direction"], cell.traffic,
                          e["max_src"], e["max_new"])
    return src


def query_flops(cell, src, result) -> float:
    """FLOPs of one finished query: its source ``src`` and the committed
    lengths of its candidates."""
    S = int((src != 0).sum())
    return flops.query(model_cfg(cell), S, [int(x) for x in result.lengths])


def check_numbers(cell, params, rec, seed: int, control: bool = False):
    """The compared numbers of this run (and the control's, if asked)."""
    picked = [(rec.queries[rid], r) for rid, (t, r) in sorted(rec.seen.items())
              if rec.finished(r) and rid in rec.queries]
    picked = check.sample(picked, seed)
    beam = cell.traffic["mode"] in ("beam", "speculative_beam")
    out = {"unfinished": float(len(rec.failed()))}
    if not picked:
        out["finished"] = 0.0
        return out
    out.update(check.numbers(params, model_cfg(cell), picked, beam,
                             cell.config["engine"]["max_new"], control))
    return out
