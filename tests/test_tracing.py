"""What the serving loop writes into a profiler trace, and the counters
behind slot occupancy.

  1. a served stream under a profiler trace records every declared
     ``serve/*`` span, each inside one pump iteration;
  2. ``op_scopes()`` joins the compiled megastep's instructions to every
     declared scope, for both step families, and the program keeps the
     module name the chip benchmark matches;
  3. ``slot_steps`` / ``capacity_steps`` count what a hand count of a
     small backlog gives, on both drives.
"""

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving import EngineConfig, StreamingEngine
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.tracing import NESTED_SCOPES, SCOPES, SPANS, op_scopes
from test_megastep import MAX_NEW, toy  # noqa: F401 (shared fixture)

PUMP = "test/pump"


def _engine(model, mode, **kw):
    ds, cfg, params = model
    ecfg = EngineConfig(mode=mode, max_new=MAX_NEW, max_src=96, n_slots=2,
                        n_beams=2, n_drafts=3, draft_len=3, paged=True,
                        page_size=8, **kw)
    return StreamingEngine(params, cfg, ds.tokenizer, ecfg)


def _host_events(path):
    """[(start, end, name)] of every host-plane event in a trace."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
    return out


# ---------------------------------------------------------------------------
# 1. host spans


def test_served_stream_records_every_span_inside_a_pump_iteration(
        toy, tmp_path):  # noqa: F811
    """Three speculative-beam requests on two paged slots: admissions,
    megasteps, syncs, evictions and results all happen, and every span
    the pump opens sits inside the ``next()`` that drove it."""
    ds, _, _ = toy
    eng = _engine(toy, "speculative_beam")
    eng.submit(ds.pair(0)[0])
    eng.serve()                               # compile outside the trace
    for i in range(1, 4):
        eng.submit(ds.pair(i)[0])
    pump = eng.serve_steps()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while True:
            with jax.profiler.TraceAnnotation(PUMP):
                try:
                    next(pump)
                except StopIteration:
                    break
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    events = _host_events(str(found[-1]))
    pumps = [(s, e) for s, e, n in events if n == PUMP]
    spans = [(s, e, n) for s, e, n in events if n.startswith("serve/")]
    assert {n for _, _, n in spans} == set(SPANS)
    for s, e, n in spans:
        assert any(ps <= s and e <= pe for ps, pe in pumps), n


# ---------------------------------------------------------------------------
# 2. named scopes of the compiled megastep


@pytest.fixture(scope="module")
def megastep_text(toy):  # noqa: F811
    """Compiled text and ``op_scopes()`` of each step family's megastep."""
    out = {}
    for mode in ("speculative", "speculative_beam"):
        eng = _engine(toy, mode)
        text = eng._megastep_fn.lower(eng.params,
                                      eng.scheduler.state).compile().as_text()
        out[mode] = (text, eng.op_scopes())
    return out


@pytest.mark.parametrize("mode", ["speculative", "speculative_beam"])
def test_op_scopes_reach_every_declared_scope(megastep_text, mode):
    """The greedy family (speculative) and the beam family (speculative
    beam) both map instructions to each top-level scope, and to each
    scope nested in ``decoder``."""
    text, scopes = megastep_text[mode]
    assert {p.split("/")[0] for p in scopes.values()} == set(SCOPES)
    nested = {p.split("/")[-1] for p in scopes.values()
              if p.startswith("decoder/")}
    assert nested == set(NESTED_SCOPES)
    # every name is an instruction of the compiled text
    assert all(f"%{name} = " in text or f" {name} = " in text
               for name in scopes)


HLO = """HloModule jit__megastep_impl, entry_computation_layout={()->f32[8]}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %reduce_sum.1 = f32[] add(%a, %b), metadata={op_name="self_attn/x"}
}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(f)/a/b"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[8]{0:T(8,128)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/decoder/while/body/ffn/tanh"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kCustom, calls=%fused_computation.1
  %c = f32[] constant(0)
  %reduce.1 = f32[] reduce(%fusion.2, %c), dimensions={0}, to_apply=%region_add, metadata={op_name="jit(f)/decoder/while/body/self_attn/reduce_sum"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.2)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy.5 = f32[8]{0:T(8,128)S(1)} copy(%x)
  %t = (s32[], f32[8]{0}) tuple(%i, %x)
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/decoder/while"}
  ROOT %select.1 = f32[8]{0} select(%m, %copy.5, %copy.5), metadata={op_name="jit(f)/cond/branch_0_fun/reorder/select_n;jit(f)/bundle/x"}
}
"""


def test_op_scopes_rules_on_compiled_text():
    """A fusion takes its metadata's scope. An instruction without one
    takes its nearest producer's (``fusion.2``, ``tuple.1``); failing
    that (``copy.5`` copies a parameter), its nearest consumer's; failing
    that, its caller's (a reducer's root, a fused root whose own name has
    no scope). A merged op name counts by its first name."""
    scopes = op_scopes(HLO)
    assert scopes["fusion.1"] == "decoder/ffn"
    assert scopes["fusion.2"] == "decoder/ffn"
    assert scopes["tuple.1"] == "decoder/ffn"
    assert scopes["copy.5"] == "reorder"
    assert scopes["reduce_sum.1"] == "decoder/self_attn"
    assert scopes["tanh.1"] == "decoder/ffn"
    assert scopes["select.1"] == "reorder"


@pytest.mark.parametrize("mode", ["speculative", "speculative_beam"])
def test_megastep_module_keeps_its_name(megastep_text, mode):
    """The chip benchmark's ``megastep_device_ms`` matches the program's
    module name: the scopes must not rename it."""
    text, _ = megastep_text[mode]
    assert text.startswith("HloModule jit__megastep_impl,")


# ---------------------------------------------------------------------------
# 3. slot occupancy


def _stub_scheduler(pipelined: bool, exhaust_at: int | None = None):
    """Two slots; a request's payload is the steps it lives. The
    pipelined variant syncs on the mask of the step it dispatched; its
    ``exhaust_at``-th dispatch finds the page pool exhausted and applies
    nothing."""
    state = {"left": {}}
    flight = {"n": 0}

    def admit(state, slot, payload):
        state["left"][slot] = payload
        return state

    def step(state):
        for s in state["left"]:
            state["left"][s] -= 1
        return state

    def finished(state):
        out = np.zeros(2, bool)
        for s, v in state["left"].items():
            out[s] = v <= 0
        return out

    def release(state, slot):
        state["left"].pop(slot, None)
        return state

    def dispatch(state):
        flight["n"] += 1
        flight["exhausted"] = flight["n"] == exhaust_at
        if not flight["exhausted"]:
            state = step(state)
        flight["mask"] = finished(state)
        return state

    def sync():
        return {"exhausted": flight["exhausted"], "finished": flight["mask"]}

    hooks = dict(dispatch=dispatch, sync=sync) if pipelined else {}
    return ContinuousScheduler(None, state, admit=admit, step=step,
                               release=release, groups={None: [0, 1]},
                               finished=finished, **hooks)


def _stub_read(state, slot):
    return dict(tokens=np.zeros((1, 1), np.int32),
                lengths=np.ones((1,), np.int32),
                logprobs=np.zeros((1,), np.float32), n_calls=0, accepted=0)


@pytest.mark.parametrize("pipelined, exhaust_at, steps, slot_steps, capacity",
                         [(True, None, 5, 8, 10), (False, None, 4, 8, 8),
                          (True, 2, 6, 11, 14)])
def test_slot_occupancy_matches_a_hand_count(pipelined, exhaust_at, steps,
                                             slot_steps, capacity):
    """Requests living 2, 4 and 2 steps, all queued at step 0 on two
    slots: 8 slot-steps of work. The legacy drive evicts before it admits
    and never leaves a slot empty: 4 steps, capacity 8. The pipelined
    drive admits before it syncs, so a slot freed at step k is refilled
    one step later: the two finishes that leave work behind (the first
    2-step request, then the 4-step one) each cost one empty slot-step,
    and the last finish none: 5 steps, capacity 10.

    With the second dispatch exhausted (both slots resident, nothing
    applied), the 4-step request, the younger by slot, is preempted and
    the step replayed with one resident; the replay finishes the first
    request, and the 4-step one restarts next to the last 2-step one, so
    the two finishes that leave work behind again cost one empty
    slot-step each: dispatches of 2, 2 (exhausted), 1 (replay), 2, 2, 1,
    1 residents, 11 slot-steps of capacity 14 over 6 synced steps."""
    sched = _stub_scheduler(pipelined, exhaust_at)
    for life in (2, 4, 2):
        sched.submit(life)
    results = sched.run(_stub_read)
    assert len(results) == 3
    assert sched.n_preemptions == (exhaust_at is not None)
    assert sched.n_steps == steps
    assert sched.slot_steps == slot_steps
    assert sched.capacity_steps == capacity
    assert sched.slot_occupancy == slot_steps / capacity


def test_engine_loop_stats_count_slot_steps(toy):  # noqa: F811
    """A lone request on a two-slot engine holds one slot through every
    megastep it is resident for, one per decoder call: half the
    capacity."""
    ds, _, _ = toy
    eng = _engine(toy, "speculative")
    rid = eng.submit(ds.pair(0)[0])
    n_calls = eng.serve()[rid].n_calls
    stats = eng.loop_stats()
    assert stats["slot_steps"] == n_calls > 0
    assert stats["capacity_steps"] == 2 * n_calls
    assert stats["slot_occupancy"] == 0.5
