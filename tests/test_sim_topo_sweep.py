"""Topology as a traced operand: padding inertness, mixed-topology batches,
mixed-latency batches, and the compile-count contract.

Mirrors tests/test_sim_padding.py (phantom flows) for the topology axis:
a fabric padded to a larger TopoDims — including a longer `prop_max` wire
ring — must run bit-identically to its unpadded self, mixed-topology and
mixed-`prop_ticks` batches must match per-case serial runs leaf-for-leaf,
and a whole (topology x latency x protocol x seed) grid must compile once
per protocol variant."""
import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import engine, scenarios, sweep, topology, workload
from repro.sim.config import BFC, DCTCP, PRESETS, SimConfig
from repro.sim.topology import ClosParams, TopoDims, pack_topo

CLOS_A = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
CLOS_B = ClosParams(n_servers=12, n_tor=2, n_spine=3,
                    switch_buffer_pkts=1024)
# same fabric shapes as CLOS_A, 3x faster wires: batches mixing it with
# CLOS_A/CLOS_B exercise the traced prop_ticks modulus
CLOS_FAST = ClosParams(n_servers=8, n_tor=2, n_spine=2, prop_ticks=4,
                       switch_buffer_pkts=512)


def _flows(topo, seed, n=40, load=0.5):
    wp = workload.WorkloadParams(workload="fb_hadoop", load=load, seed=seed)
    return workload.generate(topo, wp, n)


def _assert_states_equal(a, b, label):
    for name in a._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), \
            f"{label}: SimState.{name} differs"


def test_padded_topology_bit_identical_serial():
    """A ClosParams padded to a larger P_max/NSRV/NSW runs bit-identically
    to its unpadded serial self, leaf-for-leaf after trimming — phantom
    ports/servers/switches are inert by construction."""
    topo = topology.build(CLOS_A)
    cfg = SimConfig(proto=BFC, clos=CLOS_A)
    flows = _flows(topo, seed=3)
    n_ticks = int(flows.horizon + 1000)
    dims = TopoDims.of(topo)
    big = TopoDims(n_ports=dims.n_ports + 9, n_servers=dims.n_servers + 4,
                   n_switches=dims.n_switches + 3,
                   prop_max=dims.prop_max, hops=dims.hops)

    go = engine.compiled_runner(big, engine.static_cfg(cfg), flows.n_flows,
                                n_ticks)
    st_p, em_p, _ = go(engine.pack_flows(flows, cfg),
                       pack_topo(topo, dims=big))
    st_p = engine.SimState(*[np.asarray(x) for x in st_p])
    st_u, em_u = engine.run(topo, flows, cfg, n_ticks)

    # phantom ports/switches hold no state at all
    P, NSW = dims.n_ports, dims.n_switches
    assert (st_p.qbuf[P:] == -1).all()
    assert st_p.qtail[P:].sum() == 0 and st_p.ing_occ[P:].sum() == 0
    assert st_p.bloom_counts[P:].sum() == 0
    assert st_p.bucket_cnt[NSW:].sum() == 0
    assert not st_p.pfc_paused[P:].any()

    assert np.array_equal(np.asarray(em_p), em_u)
    _assert_states_equal(sweep.trim_state(st_p, flows.n_flows, dims),
                         sweep.trim_state(st_u, flows.n_flows, dims),
                         "padded-vs-serial")


def test_mixed_topology_batch_matches_serial():
    """Two different fabrics in ONE vmapped batch (one compilation) match
    their per-topology serial runs bit-for-bit."""
    topo_a, topo_b = topology.build(CLOS_A), topology.build(CLOS_B)
    cfg_a = SimConfig(proto=BFC, clos=CLOS_A)
    cfg_b = SimConfig(proto=BFC, clos=CLOS_B)
    fl_a, fl_b = _flows(topo_a, seed=1), _flows(topo_b, seed=2)
    n_ticks = int(max(fl_a.horizon, fl_b.horizon) + 1000)

    before = engine.trace_count()
    st, emits = sweep.run_batch([topo_a, topo_b], [fl_a, fl_b], cfg_a,
                                n_ticks)
    assert engine.trace_count() - before == 1
    for k, (topo, cfg, fl) in enumerate([(topo_a, cfg_a, fl_a),
                                         (topo_b, cfg_b, fl_b)]):
        st_s, em_s = engine.run(topo, fl, cfg, n_ticks)
        st_k = sweep.select_config(st, k, fl.n_flows, TopoDims.of(topo))
        st_s = sweep.trim_state(st_s, fl.n_flows, TopoDims.of(topo))
        assert np.array_equal(emits[k], em_s), f"lane {k} emits"
        _assert_states_equal(st_k, st_s, f"lane {k}")


@pytest.mark.slow
def test_grid_two_topos_two_protos_two_seeds_two_traces():
    """Acceptance: a (2 topologies x 2 protocols x 2 seeds) grid through
    `run_grid` triggers exactly 2 XLA traces (one per protocol variant —
    topology rides the batch axis) and matches per-config serial
    `engine.run` bit-for-bit."""
    topo_a, topo_b = topology.build(CLOS_A), topology.build(CLOS_B)
    seeds = (11, 12)
    flowsets = {(CLOS_A, s): _flows(topo_a, s, n=37) for s in seeds}
    flowsets.update({(CLOS_B, s): _flows(topo_b, s, n=37) for s in seeds})
    cases = [(f"{proto}_{clos.n_spine}sp_s{s}",
              SimConfig(proto=PRESETS[proto], clos=clos),
              flowsets[(clos, s)])
             for proto in ("bfc", "dctcp")
             for clos in (CLOS_A, CLOS_B) for s in seeds]
    n_ticks = int(max(f.horizon for f in flowsets.values()) + 1100)

    before = engine.trace_count()
    results = sweep.run_grid(topo_a, cases, n_ticks=n_ticks,
                             summarize=False)
    assert engine.trace_count() - before == 2, \
        "one compilation per protocol variant, none per topology/seed"

    for (label, cfg, flows), r in zip(cases, results):
        topo = topo_a if cfg.clos == CLOS_A else topo_b
        st_s, em_s = engine.run(topo, flows, cfg, n_ticks)
        st_s = sweep.trim_state(st_s, flows.n_flows, TopoDims.of(topo))
        assert np.array_equal(r.emits, em_s), label
        _assert_states_equal(r.state, st_s, label)


def test_prop_padding_bit_identical_serial():
    """A lane with prop_ticks=12 padded to prop_max=64 runs bit-identically
    to its unpadded serial self: wire slots beyond the true delay are never
    touched (indexing wraps at the traced modulus) and the oversized
    feedback rings are pure delay lines."""
    topo = topology.build(CLOS_A)                     # prop_ticks = 12
    cfg = SimConfig(proto=BFC, clos=CLOS_A)
    flows = _flows(topo, seed=7)
    n_ticks = int(flows.horizon + 1000)
    dims = TopoDims.of(topo)
    big = dims._replace(prop_max=64)

    go = engine.compiled_runner(big, engine.static_cfg(cfg), flows.n_flows,
                                n_ticks)
    st_p, em_p, _ = go(engine.pack_flows(flows, cfg),
                       pack_topo(topo, dims=big))
    st_p = engine.SimState(*[np.asarray(x) for x in st_p])

    # phantom wire slots hold nothing: the ring wraps at prop_ticks=12
    assert (st_p.wire_f[:, CLOS_A.prop_ticks:] == -1).all()
    assert st_p.wire_hop[:, CLOS_A.prop_ticks:].sum() == 0

    st_u, em_u = engine.run(topo, flows, cfg, n_ticks)
    assert np.array_equal(np.asarray(em_p), em_u)
    _assert_states_equal(sweep.trim_state(st_p, flows.n_flows, dims),
                         sweep.trim_state(st_u, flows.n_flows, dims),
                         "prop-padded-vs-serial")


def test_mixed_prop_ticks_batch_matches_serial():
    """Fabrics with different link delays (prop 4 / 12, different port
    counts too) in ONE vmapped batch — one compilation — match their
    per-latency serial runs bit-for-bit."""
    topo_f, topo_b = topology.build(CLOS_FAST), topology.build(CLOS_B)
    cfg_f = SimConfig(proto=BFC, clos=CLOS_FAST)
    cfg_b = SimConfig(proto=BFC, clos=CLOS_B)
    fl_f, fl_b = _flows(topo_f, seed=8), _flows(topo_b, seed=9)
    n_ticks = int(max(fl_f.horizon, fl_b.horizon) + 1000)

    assert sweep.batch_dims([topo_f, topo_b]).prop_max == 12
    before = engine.trace_count()
    st, emits = sweep.run_batch([topo_f, topo_b], [fl_f, fl_b], cfg_f,
                                n_ticks)
    assert engine.trace_count() - before == 1, \
        "mixed-latency batch must share one compilation"
    for k, (topo, cfg, fl) in enumerate([(topo_f, cfg_f, fl_f),
                                         (topo_b, cfg_b, fl_b)]):
        st_s, em_s = engine.run(topo, fl, cfg, n_ticks)
        st_k = sweep.select_config(st, k, fl.n_flows, TopoDims.of(topo))
        st_s = sweep.trim_state(st_s, fl.n_flows, TopoDims.of(topo))
        assert np.array_equal(emits[k], em_s), f"lane {k} emits"
        _assert_states_equal(st_k, st_s, f"lane {k} (prop "
                             f"{cfg.clos.prop_ticks})")


def test_latency_scenarios_expand_with_unique_labels():
    for name, protos in (("rtt_sweep", 3), ("cross_dc_latency", 2)):
        sc = scenarios.get(name)
        labels = []
        props = set()
        for label, cfg, _ in sc.cases(n_flows=10):
            labels.append(label)
            props.add(cfg.clos.prop_ticks)
        assert len(labels) == len(set(labels)) == sc.grid_size()
        assert len(props) == len(sc.topologies) >= 3
        assert sc.grid_size() == protos * len(sc.topologies)
    assert {c.prop_ticks for c in scenarios.get("rtt_sweep").topologies} \
        == {1, 4, 12, 32, 64}


def test_run_batch_chunking_matches_unchunked():
    """A max_batch_bytes budget smaller than the grid splits it into
    equal-width chunks of one shared executable, with identical results."""
    topo = topology.build(CLOS_A)
    cfg = SimConfig(proto=BFC, clos=CLOS_A)
    flowsets = [_flows(topo, seed=s, n=30) for s in (1, 2, 3)]
    n_ticks = int(max(f.horizon for f in flowsets) + 800)

    st_full, em_full = sweep.run_batch(topo, flowsets, cfg, n_ticks)
    per_lane = sweep.lane_state_bytes(TopoDims.of(topo), cfg,
                                      sweep.padded_count(flowsets), n_ticks)
    before = engine.trace_count()
    st_ch, em_ch = sweep.run_batch(topo, flowsets, cfg, n_ticks,
                                   max_batch_bytes=2 * per_lane)
    assert engine.trace_count() - before <= 1  # all chunks share one program
    assert np.array_equal(em_full, em_ch)
    _assert_states_equal(st_full, st_ch, "chunked")


def test_lane_state_bytes_scales():
    dims = TopoDims.of(topology.build(CLOS_A))
    cfg = SimConfig(proto=BFC, clos=CLOS_A)
    small = sweep.lane_state_bytes(dims, cfg, 64)
    big = sweep.lane_state_bytes(dims, cfg, 256)
    assert big > small > 0
    assert sweep.lane_state_bytes(dims, cfg, 64, n_ticks=100) \
        == small + 100 * 3 * 4


def test_topology_axis_scenarios_expand():
    sc = scenarios.get("oversub_sweep")
    cases = sc.cases(n_flows=10)
    assert len(cases) == 2 * 3          # protos x fabrics
    spines = {cfg.clos.n_spine for _, cfg, _ in cases}
    assert spines == {2, 4, 8}
    assert all("t8x" in label for label, _, _ in cases)

    fig17 = scenarios.get("fig17_incast_degree")
    cases = fig17.cases(topology.build(CLOS_B), n_flows=10)
    assert len(cases) == 3 * 5          # protos x degrees
    assert {int(lbl.rsplit("deg", 1)[1].split("_")[0])
            for lbl, _, _ in cases} == {4, 8, 16, 32, 64}
    # per-flow incast size is constant across the degree axis
    for _, _, fl in cases:
        inc = np.asarray(fl.size_pkts)[np.asarray(fl.is_incast)]
        if len(inc):
            assert (inc == fig17.incast_kb_per_flow).all()
