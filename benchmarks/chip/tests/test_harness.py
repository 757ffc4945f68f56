"""The harness: cells, configurations and metrics are found by name from
their files; no TPU means no result; the window's arithmetic on a fake
clock."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells, drive, stats
from conftest import BENCH, ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = cells.load(ROOT, name)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.traffic["mode"] in ("greedy", "speculative", "beam",
                                    "speculative_beam")
    names = [m["name"] for m in cell.metrics]
    e2e = [m["name"] for m in cell.metrics if "layer" not in m]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any("layer" in m for m in cell.metrics)
    for n in names:
        assert callable(cells.reader(n))
    assert set(cell.limits) >= {"unfinished"}
    for m in cell.metrics:
        if "layer" in m:
            assert m["moves"] in e2e


def test_every_metric_and_config_has_its_file():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]


def test_unknown_cell_is_not_found():
    with pytest.raises(cells.NotFound):
        cells.load(ROOT, "no-such-cell")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


class FakeResult:
    def __init__(self, rid, status="finished"):
        self.rid, self.status = rid, status
        self.lengths, self.n_calls = [4], 2


class FakeEngine:
    """Finishes every request ``service`` pump iterations after it was
    submitted; each iteration advances the fake clock by ``dt``."""

    def __init__(self, clock, dt, service, fail=()):
        self.clock, self.dt, self.service, self.fail = clock, dt, service, fail
        self.age, self.next_rid, self.draining = {}, 0, False

    def submit(self, q, mode=None, arrival=0.0):
        rid = self.next_rid
        self.next_rid += 1
        self.age[rid] = -1 if arrival > self.clock.t - self.clock.t0 else 0
        self.arrival = getattr(self, "arrival", {})
        self.arrival[rid] = arrival
        return rid

    def begin_drain(self):
        self.draining = True

    def serve_steps(self, realtime=True):
        while self.age:
            self.clock.t += self.dt
            now = self.clock.t - self.clock.t0
            out = []
            for rid in list(self.age):
                if self.arrival.get(rid, 0.0) > now:
                    continue
                self.age[rid] += 1
                if self.age[rid] >= self.service and rid not in self.fail:
                    del self.age[rid]
                    out.append(FakeResult(rid))
            yield out


class Clock:
    def __init__(self):
        self.t0 = self.t = 100.0

    def __call__(self):
        return self.t


class Handle(int):
    status = "running"


def test_backlog_window_arithmetic():
    clock = Clock()
    eng = FakeEngine(clock, dt=0.1, service=5)
    eng.submit = (lambda f: lambda q, mode=None, arrival=0.0:
                  Handle(f(q, mode, arrival)))(eng.submit)
    rec = drive.backlog(eng, iter(range(10**6)), "greedy", 10.0, depth=4,
                        drain_s=5.0, clock=clock)
    # 4 always queued or running; each lives 5 iterations of 0.1 s
    done = [t for t, r in rec.in_window()]
    assert all(t <= 10.0 for t in done)
    assert len(done) == pytest.approx(4 * 10.0 / 0.5, abs=4)
    assert rec.iterations == pytest.approx(100, abs=1)
    assert rec.failed() == [] and len(rec.attempted()) >= len(done)

    class Ctx:
        pass

    ctx = Ctx()
    ctx.rec = rec
    assert stats.tokens_per_call(ctx) == 2.0


def test_open_loop_counts_late_and_failed():
    clock = Clock()
    eng = FakeEngine(clock, dt=0.1, service=3, fail={2})
    due = [0.0, 1.0, 2.0, 3.0, 12.0]
    rec = drive.open_loop(eng, iter(range(5)), due, "greedy", 10.0,
                          drain_s=2.0, clock=clock)
    # the request due after the window is not owed; rid 2 never finishes
    assert sorted(rec.attempted()) == [0, 1, 2, 3]
    assert rec.failed() == [2]

    class Ctx:
        pass

    ctx = Ctx()
    ctx.rec = rec
    lat = stats.latencies(ctx)
    assert len(lat) == 4
    assert sorted(lat)[0] == pytest.approx(0.3, abs=0.11)
    # the failed one counts as seen at the end of the drain
    assert max(lat) == pytest.approx(rec.seconds + rec.drain_s - 2.0,
                                     abs=0.11)
