"""The model code found by family and the serving front found by name:
the seq2seq family gives ``mt-retro.sbs.batch`` what the harness built
before the family and front were found by name (the ``parent_*``
functions below are that code, copied), and a mix without ``front``
takes the old path."""

import numpy as np
import pytest

import run as R
from bench import cells, chem, engines, flops, traffic
from conftest import ROOT


# ---- the harness's code before families and fronts, copied
def parent_model_cfg(cell):
    c = cell.config
    return {k: c[k] for k in ("d_model", "n_heads", "d_ff", "vocab_size",
                              "n_encoder_layers", "n_decoder_layers",
                              "direction")}


def parent_program_cfg(cell):
    from repro.configs.base import ModelConfig

    c = cell.config
    return ModelConfig(
        name=cell.config_entry["name"], family="seq2seq",
        n_layers=c["n_decoder_layers"], n_encoder_layers=c["n_encoder_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"], n_kv_heads=c["n_heads"],
        d_ff=c["d_ff"], vocab_size=c["vocab_size"], use_bias=True,
        norm="layernorm", gated_ffn=False, pos="sinusoidal",
        max_len=c["max_positions"])


def parent_engine_config(cell):
    from repro.serving import EngineConfig

    e = cell.config["engine"]
    return EngineConfig(
        mode=cell.traffic["mode"],
        n_slots=int(e["n_slots"][cell.traffic["mode"]]),
        n_beams=e["n_beams"], n_drafts=e["n_drafts"],
        draft_len=e["draft_len"], max_new=e["max_new"],
        max_src=e["max_src"], paged=True, page_size=e["page_size"])


def parent_queries(cell, seed):
    e = cell.config["engine"]
    src, _ = traffic.pool(cell.config["direction"], cell.traffic,
                          e["max_src"], e["max_new"])
    return src, np.random.default_rng(seed).permutation(len(src))


def parent_query_flops(cell, src, result):
    S = int((src != 0).sum())
    return flops.query(parent_model_cfg(cell), S,
                       [int(x) for x in result.lengths])


class Res:
    def __init__(self, lengths):
        self.lengths = lengths


SEEDS = [7, 2**31 + 9, 2**40 + 3]


@pytest.fixture(scope="module")
def retro():
    return cells.load(ROOT, "mt-retro.sbs.batch")


def test_family_and_front_of_mt_retro(retro):
    fam = cells.family(retro)
    assert fam.__file__.endswith("families/seq2seq.py")
    assert cells.front(retro).__file__.endswith("fronts/engine.py")
    assert fam.model_cfg(retro) == parent_model_cfg(retro)
    assert fam.program_cfg(retro) == parent_program_cfg(retro)
    assert engines.engine_config(retro) == parent_engine_config(retro)
    assert isinstance(fam.tokenizer(), chem.Tokenizer)


@pytest.mark.parametrize("seed", SEEDS)
def test_mt_retro_pool_order_and_flops_as_before(retro, seed):
    fam = cells.family(retro)
    src, order = R.queries(retro, fam, seed)
    src0, order0 = parent_queries(retro, seed)
    assert np.array_equal(src, src0) and np.array_equal(order, order0)
    rng = np.random.default_rng(seed)
    for i in order[:8]:
        r = Res(rng.integers(1, 97, size=5))
        assert fam.query_flops(retro, src[i], r) == \
            parent_query_flops(retro, src[i], r)


def test_family_not_found_is_refused(retro):
    import copy
    other = copy.copy(retro)
    other.config = dict(retro.config, family="no-such-family")
    with pytest.raises(cells.NotFound):
        cells.family(other)
    other.traffic = dict(retro.traffic, front="no-such-front")
    with pytest.raises(cells.NotFound):
        cells.front(other)
