"""Finds everything by name: a cell's entry in ``BENCHMARK.json``, its
configuration file, its traffic mix (``traffic/<name>.json``), its limits
(``checks/<cell>.json``), each metric's reader (``metrics/<metric>.py``),
the model code of the configuration's family
(``bench/families/<family>.py``) and the serving front the mix names
(``bench/fronts/<front>.py``). Adding a configuration, a family, a front,
a mix, a cell or a metric is adding files; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NotFound(KeyError):
    pass


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise NotFound(f"missing {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict        # the configuration file
    config_entry: dict  # its entry in BENCHMARK.json
    traffic: dict
    limits: dict
    metrics: list       # metric entries this cell reports, end_to_end first


def applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    """Whether ``metric`` is reported in ``cell``: by its ``workloads``
    list, else (per-layer) wherever its ``moves`` metric is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric and reported is not None:
        return metric["moves"] in reported
    return True


def load(root: str, name: str, bench_dir: str = HERE) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in spec["workloads"] if w["name"] == name]
    if not wl:
        raise NotFound(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    entry = [c for c in spec["configs"] if c["name"] == wl["config"]]
    if not entry:
        raise NotFound(f"no config {wl['config']!r} in BENCHMARK.json")
    entry = entry[0]
    e2e = [m for m in spec["end_to_end"] if applies(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if applies(m, name, names)]
    return Cell(
        name=name, workload=wl, config_entry=entry,
        config=_json(os.path.join(root, entry["file"])),
        traffic=_json(os.path.join(bench_dir, "traffic",
                                   wl["traffic"] + ".json")),
        limits=_json(os.path.join(bench_dir, "checks", name + ".json")),
        metrics=e2e + layer)


def _module(kind: str, name: str, bench_dir: str):
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded once a process."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise NotFound(f"no {kind} module {path}")
    mod_name = "bench_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in f"{kind}_{name}")
    mod = sys.modules.get(mod_name)
    if mod is None or getattr(mod, "__file__", None) != path:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return mod


def reader(metric: str, bench_dir: str = HERE):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module("metrics", metric, bench_dir).read


def family(cell: Cell, bench_dir: str = HERE):
    """The model code of the cell's configuration: its ``family`` key,
    ``seq2seq`` where the file has none."""
    return _module(os.path.join("bench", "families"),
                   cell.config.get("family", "seq2seq"), bench_dir)


def front(cell: Cell, bench_dir: str = HERE):
    """The serving front the cell's mix names: its ``front`` key,
    ``engine`` (the in-process pump) where the mix has none."""
    return _module(os.path.join("bench", "fronts"),
                   cell.traffic.get("front", "engine"), bench_dir)
