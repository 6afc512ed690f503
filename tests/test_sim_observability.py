"""What the program records about itself: the named scopes in the runner's
program (op_name metadata a device trace shows per op) and the host spans
around each stage of a sweep call, with `LAST_TIMING` read from them."""
import re

import jax
import pytest

from repro.sim import engine, sweep, topology, workload
from repro.sim.config import BFC, DCQCN, SimConfig
from repro.sim.exec import dispatch
from repro.sim.exec.dispatch import Span, self_seconds, span
from repro.sim.topology import ClosParams, TopoDims

pytestmark = pytest.mark.tier1

CLOS = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
PHASES = ("derive", "control", "switch_tx", "nic_tx", "arrivals",
          "feedback", "stats")
SCOPE = re.compile(r"(?<![\w.])((?:phase|runner)\.\w+|switch_decision)")


@pytest.fixture(scope="module")
def topo():
    return topology.build(CLOS)


def _flows(topo, seed, n=24):
    wp = workload.WorkloadParams(workload="uniform", load=0.5, seed=seed)
    return workload.generate(topo, wp, n)


def _paths(topo, impl):
    """The scope paths of the lowered batched runner (a horizon off the
    segment grid, so the remainder, the predicate and the tail are all
    in it)."""
    from dataclasses import replace
    cfg = SimConfig(proto=replace(BFC, kernel_impl=impl), clos=CLOS)
    dims = TopoDims.of(topo)
    go = engine.compiled_runner(dims, cfg, 64, 600, batched=True,
                                segment=256)
    low = go.lower(sweep.stack_operands([_flows(topo, 1)], cfg, 64),
                   sweep.stack_topos([topo], cfg, dims))
    # op_name metadata is built from these locations
    return set(re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True)))


@pytest.mark.parametrize("impl", ["lax", "interpret"])
def test_runner_names_every_phase_and_runner_scope(topo, impl):
    found = {m for p in _paths(topo, impl) for m in SCOPE.findall(p)}
    want = {f"phase.{p}" for p in PHASES} | {
        "runner.quiescent", "runner.emit_write", "runner.tail"}
    assert want <= found, want - found


@pytest.mark.parametrize("impl, phases", [
    ("lax", {"phase.derive", "phase.switch_tx"}),
    ("interpret", {"phase.derive"})])
def test_switch_decision_scope_on_both_paths(topo, impl, phases):
    """The decision is one scope inside its phase: the threshold and pick
    on the lax path, the fused kernel on the kernel path."""
    under = {m for p in _paths(topo, impl) if "switch_decision" in p
             for m in SCOPE.findall(p) if m.startswith("phase.")}
    assert under == phases


def test_span_record_nests_and_shares_a_call():
    with span("outer", lanes=3) as a:
        with span("mid") as b:
            with span("inner", bytes=7):
                pass
        with span("mid"):
            pass
    rec = dispatch.last_spans()
    assert [s.name for s in rec] == ["outer", "mid", "inner", "mid"]
    assert [s.parent for s in rec] == [None, 0, 1, 0]
    assert rec[0] is a and rec[1] is b
    assert rec[0].counts == {"lanes": 3} and rec[2].counts == {"bytes": 7}
    assert all(s.dur_ns >= 0 for s in rec)
    with span("next"):
        # the open call is not the last finished one
        assert dispatch.last_spans() is rec
    assert [s.name for s in dispatch.last_spans()] == ["next"]


def test_spans_share_a_call_id_in_the_profiler_trace(tmp_path):
    """Every span of one top-level call carries one `call` stat, a new
    call a new one, and counts travel as stats."""
    jax.profiler.start_trace(str(tmp_path))
    with span("repro.test.a", lanes=2):
        with span("repro.test.b", bytes=5):
            pass
    with span("repro.test.c"):
        pass
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    stats = {e.name: dict(e.stats) for plane in pd.planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.test.")}
    assert stats["repro.test.a"]["call"] == stats["repro.test.b"]["call"]
    assert stats["repro.test.c"]["call"] != stats["repro.test.a"]["call"]
    assert stats["repro.test.a"]["lanes"] == 2
    assert stats["repro.test.b"]["bytes"] == 5


def test_self_seconds():
    rec = [Span("call", None, 0, 10_000_000_000),
           Span("a", 0, 1_000_000_000, 4_000_000_000),
           Span("b", 1, 2_000_000_000, 1_000_000_000),
           Span("a", 0, 6_000_000_000, 2_000_000_000),
           Span("c", None, 20_000_000_000, 1_000_000_000)]
    assert self_seconds(rec, rec[0]) == pytest.approx(
        {"call": 4.0, "a": 5.0, "b": 1.0})
    assert self_seconds(rec, rec[1]) == pytest.approx({"a": 3.0, "b": 1.0})


def test_grid_records_every_stage_and_sources_last_timing(topo):
    """A two-protocol grid: each group's stages nest under it, one
    `LAST_TIMING` per execute with its wall equal to the execute span, and
    the readback carries the bytes it copied."""
    cases = [(f"bfc{i}", SimConfig(proto=BFC, clos=CLOS), _flows(topo, i))
             for i in range(2)]
    cases.append(("dcqcn", SimConfig(proto=DCQCN, clos=CLOS),
                  _flows(topo, 5)))
    sweep.run_grid(topo, cases, n_ticks=200)
    rec = dispatch.last_spans()
    names = [s.name for s in rec]
    assert names[0] == "repro.sweep.run_grid"
    assert rec[0].counts == {"lanes": 3}

    def parent(s):
        return rec[s.parent].name

    groups = [s for s in rec if s.name == "repro.sweep.group"]
    assert [g.counts["lanes"] for g in groups] == [2, 1]
    assert all(parent(g) == "repro.sweep.run_grid" for g in groups)
    for name, up in [("repro.exec.plan", "repro.sweep.group"),
                     ("repro.dispatch.execute", "repro.sweep.group"),
                     ("repro.sweep.select", "repro.sweep.group"),
                     ("repro.sweep.summarize", "repro.sweep.group"),
                     ("repro.dispatch.stack", "repro.dispatch.execute"),
                     ("repro.dispatch.launch", "repro.dispatch.execute"),
                     ("repro.dispatch.wait", "repro.dispatch.execute"),
                     ("repro.dispatch.readback", "repro.dispatch.execute")]:
        spans = [s for s in rec if s.name == name]
        assert spans and all(parent(s) == up for s in spans), name
    assert names.count("repro.sweep.summarize") == 3
    stack = next(s for s in rec if s.name == "repro.dispatch.stack")
    assert set(stack.counts) == {"lanes", "padded_lanes", "chunk"}

    # the last group's landing copied its chunk's whole padded state, its
    # emit rows and its active tick counts
    readback = [s for s in rec if s.name == "repro.dispatch.readback"][-1]
    plan = dispatch.last_plan()
    assert readback.counts["bytes"] == plan.chunk_width * (
        sweep.lane_state_bytes(plan.dims, cases[2][1], plan.f_max,
                               plan.n_ticks) + 4)

    timing = dispatch.last_timing()
    assert set(timing) == {
        "tag", "kernel_impl", "wall_s", "lanes", "n_ticks",
        "active_ticks_total", "retries", "chunks_reused", "budget_source",
        "devices", "out_devices", "stages"}
    execute = [s for s in rec if s.name == "repro.dispatch.execute"][-1]
    assert timing["wall_s"] == execute.dur_ns / 1e9
    assert timing["tag"] == "dcqcn" and timing["lanes"] == 1
    assert set(timing["stages"]) >= {
        "repro.dispatch.execute", "repro.dispatch.stack",
        "repro.dispatch.launch", "repro.dispatch.wait",
        "repro.dispatch.readback"}
    assert sum(timing["stages"].values()) == pytest.approx(
        timing["wall_s"], rel=1e-9)
    assert all(v >= 0 for v in timing["stages"].values())
