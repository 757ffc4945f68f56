"""Finds everything by name: a cell's entry in ``BENCHMARK.json``, its
configuration file, its traffic mix (``traffic/<name>.json``), its limits
(``checks/<cell>.json``) and each metric's reader
(``metrics/<metric>.py``). Adding a configuration, a mix, a cell or a
metric is adding files; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

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


def reader(metric: str, bench_dir: str = HERE):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    if not os.path.isfile(path):
        raise NotFound(f"no reader {path}")
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
