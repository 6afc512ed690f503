"""A fat-tree through the program's normal path: `sweep.run_grid` mixing a
leaf-spine lane (4-hop routes) with a k = 4 fat-tree lane (6 hops) in one
program equals each lane run alone; the planner's span records the shapes
and lane state of the program it plans, which the benchmark's
`planner.lane_state_mib` reads."""
import sys
from dataclasses import asdict
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import engine, sweep, topology, workload
from repro.sim.config import BFC, SimConfig
from repro.sim.exec import dispatch
from repro.sim.topology import ClosParams, FatTreeParams, TopoDims

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

LEAF_SPINE = ClosParams(n_servers=8, n_tor=2, n_spine=2,
                        switch_buffer_pkts=512)
FAT_TREE = FatTreeParams(k=4, switch_buffer_pkts=512)
N_TICKS = 400


def _case(params, seed):
    topo = topology.build(params)
    wp = workload.WorkloadParams(workload="uniform", load=0.7, seed=seed,
                                 incast_load=0.2, incast_degree=6,
                                 incast_total_kb=1024)
    return (f"{type(params).__name__}_s{seed}",
            SimConfig(proto=BFC, clos=params),
            workload.generate(topo, wp, 40))


def _plan_span(spans):
    plans = [s for s in spans if s.name == "repro.exec.plan"]
    assert len(plans) == 1
    return plans[0].counts


@pytest.fixture(scope="module")
def runs():
    """Each lane alone, then both in one call, with the plan span's counts
    and the benchmark reader's value after each call."""
    import harness
    read = harness.reader("planner.lane_state_mib")
    ls, ft = _case(LEAF_SPINE, 3), _case(FAT_TREE, 4)
    ls_topo = topology.build(LEAF_SPINE)
    out = {}
    for name, topo, cases in [("leaf_spine", ls_topo, [ls]),
                              ("fat_tree", topology.build(FAT_TREE), [ft]),
                              ("mixed", ls_topo, [ls, ft])]:
        res = sweep.run_grid(topo, cases, n_ticks=N_TICKS)
        out[name] = (res, _plan_span(dispatch.last_spans()), read(None))
    return out


def test_mixed_fabric_batch_equals_each_lane_alone(runs):
    mixed, counts, _ = runs["mixed"]
    assert counts["hops"] == 6 and counts["lanes"] == 2
    for k, alone in enumerate(("leaf_spine", "fat_tree")):
        (ref,), _, _ = runs[alone]
        got = mixed[k]
        for name in ref.state._fields:
            assert np.array_equal(np.asarray(getattr(got.state, name)),
                                  np.asarray(getattr(ref.state, name))), \
                f"{alone}: SimState.{name} differs"
        assert np.array_equal(got.emits, ref.emits)
        np.testing.assert_equal(asdict(got.metrics), asdict(ref.metrics))
    # the leaf-spine lane keeps its own route width once trimmed, and the
    # fat-tree's backpressure fired
    assert mixed[0].state.f_q.shape[1] == 4
    assert mixed[1].state.f_q.shape[1] == 6
    assert int(mixed[1].state.stat_pauses) > 0


@pytest.mark.parametrize("fabric, shape", [
    ("leaf_spine", (24, 8, 4, 4)), ("fat_tree", (96, 16, 20, 6)),
    ("mixed", (96, 16, 20, 6))])
def test_plan_span_records_the_program_shapes(runs, fabric, shape):
    """The `repro.exec.plan` span carries the shapes that key the compiled
    program and the planner's bytes a lane; `planner.lane_state_mib`
    reads the latter."""
    (res, counts, mib) = runs[fabric]
    ports, servers, switches, hops = shape
    assert (counts["ports"], counts["servers"], counts["switches"],
            counts["hops"]) == (ports, servers, switches, hops)
    f_max = sweep.padded_count([r.flows for r in res])
    assert counts["flows"] == f_max
    dims = TopoDims(n_ports=ports, n_servers=servers, n_switches=switches,
                    prop_max=12, hops=hops)
    assert counts["lane_bytes"] == sweep.lane_state_bytes(
        dims, engine.static_cfg(res[0].cfg), f_max, N_TICKS)
    assert mib == counts["lane_bytes"] / 2 ** 20


def test_lane_state_grows_with_the_route_width(runs):
    assert runs["fat_tree"][1]["lane_bytes"] > \
        runs["leaf_spine"][1]["lane_bytes"]


def test_lane_state_metric_is_silent_without_the_count(monkeypatch):
    """Against a program whose plan span has no `lane_bytes` (an older
    one), the reader returns nothing rather than raising."""
    import harness
    import scopes
    read = harness.reader("planner.lane_state_mib")
    span = scopes.Span(0, 1, "repro.exec.plan", {"lanes": 1})
    monkeypatch.setattr(scopes, "last_call_spans", lambda: [span])
    assert read(None) is None
    monkeypatch.setattr(scopes, "last_call_spans", lambda: None)
    assert read(None) is None
