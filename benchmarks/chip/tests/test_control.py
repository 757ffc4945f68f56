"""The control: the plain reference computed in bfloat16, put in the
program's place, read by the same comparison that decides ``correct``.

Here, at a size a CPU test holds, it must read far above the program,
which runs float32 on the CPU. On the chip, at the cell's own size, its
readings and the program's were recorded (``calibration/<cell>.json``,
from ``calibrate.py control`` and the benchmark's own runs); the limits
in ``checks/<cell>.json`` must pass every recorded run of the program
and fail every recorded run of the control."""

import glob
import json
import os

import jax
import pytest

import run as R
import tiny
from bench import flops
from conftest import BENCH


@pytest.fixture(autouse=True)
def cpu_peak(monkeypatch):
    monkeypatch.setattr(flops, "peak", lambda kind: {"bf16_flops_per_s": 1e12})


@pytest.mark.parametrize("mode", ["speculative", "speculative_beam"])
def test_control_reads_far_above_the_program(trained, mode):
    cell = tiny.cell(mode, "backlog", drain_s=5)
    res = R.run(cell, 2**34 + 7, 2.0, False, jax.devices()[:1],
                control=True)
    assert res["correct"], res["checks"]
    program = {k: v["value"] for k, v in res["checks"].items()}
    control = {k.split(".", 1)[1]: v for k, v in res["control"].items()}
    assert control and set(control) <= set(program)
    # at least one number separates the two by 3x or more
    assert any(v > 0 and v >= 3 * program[k] for k, v in control.items())
    # and the control fails a limit set between them
    limits = {k: (program[k] + v) / 2 for k, v in control.items()}
    ok, _ = R.verdict(control, dict(limits, unfinished=0))
    assert not ok


CALIBRATED = sorted(glob.glob(os.path.join(BENCH, "calibration", "*.json")))


@pytest.mark.parametrize("path", CALIBRATED, ids=os.path.basename)
def test_committed_limits_split_the_chip_readings(path):
    name = os.path.basename(path)[:-len(".json")]
    with open(os.path.join(BENCH, "checks", name + ".json")) as f:
        limits = json.load(f)
    with open(path) as f:
        readings = json.load(f)
    assert len({r["seed"] for r in readings["program"]}) >= 12
    assert len({r["seed"] for r in readings["control"]}) >= 3
    for r in readings["program"]:
        ok, shown = R.verdict(r["numbers"], limits)
        assert ok, (r["seed"], shown)
    for r in readings["control"]:
        numbers = dict(r["numbers"], unfinished=0.0)
        ok, shown = R.verdict(numbers, limits)
        assert not ok, (r["seed"], shown)


def test_every_cell_has_its_chip_readings():
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    have = {os.path.basename(p)[:-len(".json")] for p in CALIBRATED}
    assert {w["name"] for w in spec["workloads"]} <= have
