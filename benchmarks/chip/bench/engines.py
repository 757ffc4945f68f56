"""The program's serving engine, built and warmed for a cell: what every
serving front (``bench/fronts/``) and the calibration tools share. Only
this module, the fronts and each family's ``program_cfg`` import the
program."""

from __future__ import annotations


def slots(cell) -> int:
    """The configuration's slot count for the mix's mode."""
    return int(cell.config["engine"]["n_slots"][cell.traffic["mode"]])


def engine_config(cell, n_slots: int | None = None):
    """The ``EngineConfig`` of the configuration's ``engine`` block: one
    paged slot group of the mix's mode."""
    from repro.serving import EngineConfig

    e = cell.config["engine"]
    return EngineConfig(
        mode=cell.traffic["mode"], n_slots=n_slots or slots(cell),
        n_beams=e["n_beams"], n_drafts=e["n_drafts"],
        draft_len=e["draft_len"], max_new=e["max_new"],
        max_src=e["max_src"], paged=True, page_size=e["page_size"])


def make_engine(cell, fam, params, n_slots: int | None = None):
    """One ``StreamingEngine`` of the family's model."""
    from repro.serving import StreamingEngine

    return StreamingEngine(params, fam.program_cfg(cell), fam.tokenizer(),
                           engine_config(cell, n_slots))


def warm(eng, cell, src, order, n_slots: int) -> None:
    """Serve ``n_slots`` + 1 requests from the end of the seed's order to
    completion through the same pump: every program the window runs
    (admission, megastep, release, read-out) is compiled or loaded here."""
    for i in order[-(n_slots + 1):]:
        eng.submit(src[i], mode=cell.traffic["mode"])
    eng.serve(realtime=True)
