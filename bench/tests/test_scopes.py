"""The program's own names in a trace: device time by phase scope, host
time by span, idle gaps by span, and the span readers."""
import gzip
import json
import re
from pathlib import Path

import pytest

import devtrace
import harness
import scopes
from devtrace import Event, Recording
from scopes import Span

DERIVE = "jit(go)/vmap()/while/body/while/body/closed_call/phase.derive"


def hand_built():
    # a window of 1 ms on one device: a while loop holding five ops, one
    # of them the decision nested in derive, one under no scope; a 100 us
    # gap inside the readback span and a 200 us one outside every span
    dev0 = [Event(0, 1_000_000, "%while.1 = (s32[]) while(...)"),
            Event(10_000, 30_000, "%fusion.1 = s32[8] fusion(...)"),
            Event(30_000, 35_000, "%_fused.2 = s32[8] custom-call(...)"),
            Event(40_000, 100_000, "%fusion.3 = s32[8] fusion(...)"),
            Event(200_000, 210_000, "%copy.4 = s32[8] copy(...)"),
            Event(210_000, 800_000, "%fusion.5 = pred[8] fusion(...)")]
    scope = {
        "%fusion.1 = s32[8] fusion(...)": DERIVE + "/add",
        "%_fused.2 = s32[8] custom-call(...)":
            DERIVE + "/switch_decision/pallas_call",
        "%fusion.3 = s32[8] fusion(...)":
            "jit(go)/vmap()/while/body/while/body/phase.stats/scatter-add",
        "%copy.4 = s32[8] copy(...)": "jit(go)/vmap()/while",
        "%fusion.5 = pred[8] fusion(...)":
            "jit(go)/vmap(runner.quiescent)/reduce_and"}
    spans = [Span(0, 1_000_000, "repro.sweep.run_grid", {"lanes": 2}),
             Span(0, 900_000, "repro.dispatch.execute", {"lanes": 2}),
             Span(0, 5_000, "repro.dispatch.stack", {"lanes": 2}),
             Span(100_000, 200_000, "repro.dispatch.readback",
                  {"bytes": 64}),
             Span(920_000, 930_000, "repro.sweep.summarize", {"case": 0}),
             Span(940_000, 960_000, "repro.sweep.summarize", {"case": 1})]
    host = [Event(0, 1_000_000, devtrace.WINDOW_SPAN)]
    return Recording(devices={0: dev0}, host=host), scope, spans


def test_scope_of():
    assert scopes.scope_of(DERIVE + "/switch_decision/min") == \
        "phase.derive"
    assert scopes.scope_of("jit(go)/vmap(runner.tail)/while/body/sub") == \
        "runner.tail"
    # the traced tail evaluates the step once: its ops are the phase's
    assert scopes.scope_of("jit(go)/vmap(runner.tail)/phase.stats/add") \
        == "phase.stats"
    assert scopes.scope_of("jit(go)/vmap()/while") == ""


def test_device_seconds_by_phase():
    rec, scope, _ = hand_built()
    secs = scopes.device_seconds(rec, scope, [0])
    assert secs == pytest.approx({
        "phase.derive": 25e-6, "phase.stats": 60e-6, "": 10e-6,
        "runner.quiescent": 590e-6})
    per = scopes.phase_us_per_lane_tick(secs, lane_ticks=5)
    assert per["derive"] == pytest.approx(5.0)
    assert per["stats"] == pytest.approx(12.0)
    assert per["arrivals"] == 0.0 and len(per) == 7
    assert scopes.covered_share(secs) == pytest.approx(675 / 685)
    # a program without scopes reads nothing
    assert scopes.phase_us_per_lane_tick({"": 1.0}, 5) is None


def test_span_self_time_and_per_lane():
    _, _, spans = hand_built()
    own = scopes.self_seconds(spans)
    assert own == pytest.approx({
        "repro.sweep.run_grid": 70e-6, "repro.dispatch.execute": 795e-6,
        "repro.dispatch.stack": 5e-6, "repro.dispatch.readback": 100e-6,
        "repro.sweep.summarize": 30e-6})
    assert sum(own.values()) == pytest.approx(1e-3)
    assert scopes.ms_per_lane(spans, ["repro.dispatch.readback"]) == \
        pytest.approx(0.05)
    assert scopes.ms_per_lane(spans[1:], ["repro.dispatch.stack"]) is None


def test_idle_by_span():
    rec, _, spans = hand_built()
    idle = scopes.idle_by_span(rec, spans, [0])
    # busy [10, 35] + [40, 100] + [200, 800] us: gaps of 10 and 5 us
    # (under 50 us, left out), 100 us in the readback, and 200 us after
    # the last op, whose middle lies in the sweep call after the execute
    assert idle == pytest.approx({"repro.dispatch.readback": 100e-6,
                                  "repro.sweep.run_grid": 200e-6})
    idle = scopes.idle_by_span(rec, spans[3:], [0])
    assert idle == pytest.approx({"repro.dispatch.readback": 100e-6,
                                  scopes.OUTSIDE: 200e-6})


def test_op_names_of_a_compiled_program():
    """The fallback for a trace whose events carry no path: instruction
    name to op_name from the program's own optimized HLO."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("phase.derive"):
            y = jnp.sort(x * 2)
        with jax.named_scope("phase.stats"):
            return jnp.cumsum(y)
    text = jax.jit(f).lower(jnp.ones(16)).compile().as_text()
    names = scopes.op_names(text)
    paths = set(names.values())
    assert any("phase.derive" in p for p in paths)
    assert any("phase.stats" in p for p in paths)
    # the entry's sort (its comparator's parameters carry no scope)
    sort = next(n for n, p in names.items()
                if n.startswith("sort") and "phase." in p)
    event = f"%{sort} = f32[16] sort()"
    rec = Recording(devices={0: [Event(0, 1, event)]}, host=[])
    assert scopes.scope_of(scopes.scopes_from_hlo(rec, text)[event]) == \
        "phase.derive"


HLO = """HloModule jit_go, is_scheduled=true

%fused_computation.6 (param_0: s32[8]) -> pred[8] {
  %param_0 = s32[8]{0} parameter(0)
  %gather.1 = s32[8]{0} gather(%param_0), metadata={op_name="gather" stack_frame_id=2}
  ROOT %compare.1 = pred[8]{0} compare(%gather.1), metadata={op_name="gt" stack_frame_id=2}
}

ENTRY %main.1 (p: s32[8]) -> pred[8] {
  %p = s32[8]{0} parameter(0), metadata={op_name="p"}
  %add.2 = s32[8]{0} add(%p, %p), metadata={op_name="jit(go)/while/body/phase.stats/add" stack_frame_id=1}
  %fusion.3 = pred[8]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.6
  ROOT %copy.4 = pred[8]{0} copy(%fusion.3)
}

FileNames
1 "/src/repro/sim/phases/ctx.py"

FunctionNames
1 "derive"
2 "phase.control"

FileLocations
1 {file_name_id=1 function_name_id=1 line=307 end_line=307 column=14 end_column=14}
2 {file_name_id=1 function_name_id=2 line=54 end_line=54 column=19 end_column=19}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
"""


def test_op_names_from_frames_and_fusions():
    """Short locations put the scope in the stack frame's function name,
    not in op_name; a fusion without metadata takes the scope of what it
    calls; an op under no scope stays unscoped."""
    paths = scopes.op_names(HLO)
    assert scopes.scope_of(paths["gather.1"]) == "phase.control"
    assert scopes.scope_of(paths["add.2"]) == "phase.stats"
    assert scopes.scope_of(paths["fusion.3"]) == "phase.control"
    assert scopes.scope_of(paths["copy.4"]) == ""
    rec = Recording(devices={0: [Event(0, 1, "%fusion.3 = pred[8] fusion()"),
                                 Event(1, 3, "%copy.4 = pred[8] copy()")]},
                    host=[])
    assert scopes.scopes_from_hlo(rec, HLO) == {
        "%fusion.3 = pred[8] fusion()": paths["fusion.3"],
        "%copy.4 = pred[8] copy()": ""}


@pytest.mark.parametrize("metric, names", [
    ("dispatch.stack_ms_per_lane", ["repro.dispatch.stack"]),
    ("dispatch.readback_ms_per_lane", ["repro.dispatch.readback"]),
    ("sweep.summarize_ms_per_lane", ["repro.sweep.summarize"])])
def test_span_readers(metric, names, monkeypatch):
    """Each reader reads the program's in-memory record of its last call,
    and nothing where the program keeps no record."""
    from repro.sim.exec import dispatch
    _, _, spans = hand_built()
    parents = [None, 0, 1, 1, 0, 0]
    monkeypatch.setattr(dispatch, "LAST_SPANS", [
        dispatch.Span(s.name, up, int(s.start_ns),
                      int(s.end_ns - s.start_ns), dict(s.stats))
        for s, up in zip(spans, parents)])
    read = harness.reader(metric)
    assert read(None) == pytest.approx(
        scopes.ms_per_lane(spans, names))
    monkeypatch.delattr(dispatch, "last_spans")
    assert read(None) is None


RECORDED = Path(__file__).resolve().parent / "data" / \
    "trace_bfc_x1_scoped.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_scoped_trace():
    """A slice of a chip trace of the BFC cell with the program's scopes
    and spans: every op of the tick's loop names its phase, the phases
    read as when recorded, and the spans nest into the stages."""
    doc = json.loads(gzip.open(RECORDED, "rt").read())
    rec = Recording(devices={int(d): [Event(*e) for e in evs]
                             for d, evs in doc["devices"].items()},
                    host=[Event(*doc["window"], devtrace.WINDOW_SPAN)])
    spans = [Span(*s) for s in doc["spans"]]
    secs = scopes.device_seconds(rec, doc["scopes"], [0])
    assert secs == pytest.approx(doc["device_s"], rel=1e-9)
    assert scopes.covered_share(secs) >= 0.9
    per = scopes.phase_us_per_lane_tick(secs, doc["lane_ticks"])
    assert all(v >= 0 for v in per.values()) and per["derive"] > 0
    assert any(re.search(r"(?<![\w.])switch_decision", p)
               for p in doc["scopes"].values())
    own = scopes.self_seconds(spans)
    assert own == pytest.approx(doc["span_self_s"], rel=1e-9)
    assert scopes.lanes_of(spans) == 1
