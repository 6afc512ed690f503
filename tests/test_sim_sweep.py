"""Batched sweep subsystem: one compilation per grid, bit-identical to
serial runs, and packet conservation across registry scenarios."""
import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import engine, scenarios, sweep, topology, workload
from repro.sim.config import BFC, BFC_NO_BUFOPT, PRESETS, SimConfig
from repro.sim.topology import ClosParams, TopoDims, pack_topo

CLOS = ClosParams(n_servers=16, n_tor=2, n_spine=2, switch_buffer_pkts=2048)


@pytest.fixture(scope="module")
def tiny_topo():
    return topology.build(CLOS)


def _fb_grid(topo, loads=(0.4, 0.6), seeds=(1, 2, 3, 4), n_flows=60):
    return [workload.generate(
                topo, workload.WorkloadParams(workload="fb_hadoop",
                                              load=load, seed=seed),
                n_flows)
            for load in loads for seed in seeds]


@pytest.mark.slow
def test_grid_one_compilation_and_bitwise_match(tiny_topo):
    """Acceptance: a 4-seed x 2-load fb_hadoop sweep through sim/sweep.py
    triggers exactly ONE XLA compilation and matches per-config serial
    `engine.run` results bit-for-bit on every SimState leaf + emits.
    (slow: the 8 serial reference re-runs dominate; the one-compilation
    property alone is covered tier-1 by test_serial_runs_share_one_...)"""
    topo = tiny_topo
    cfg = SimConfig(proto=BFC, clos=CLOS)
    flowsets = _fb_grid(topo)
    assert len(flowsets) == 8
    n_ticks = int(max(f.horizon for f in flowsets) + 3000)

    before = engine.trace_count()
    st_b, em_b = sweep.run_batch(topo, flowsets, cfg, n_ticks)
    assert engine.trace_count() - before == 1, \
        "the whole 8-point grid must compile exactly once"

    for k, flows in enumerate(flowsets):
        st_s, em_s = engine.run(topo, flows, cfg, n_ticks)
        st_k = sweep.select_config(st_b, k, flows.n_flows)
        st_s = sweep.trim_state(st_s, flows.n_flows)  # no-op shape align
        assert np.array_equal(em_b[k], em_s), f"emits differ in lane {k}"
        for name in st_s._fields:
            a = np.asarray(getattr(st_s, name))
            b = np.asarray(getattr(st_k, name))
            assert np.array_equal(a, b), \
                f"SimState.{name} differs in lane {k}"


def test_serial_runs_share_one_compilation(tiny_topo):
    """Same-shaped serial runs reuse the cached executable (no per-seed
    recompiles)."""
    topo = tiny_topo
    cfg = SimConfig(proto=BFC, clos=CLOS)
    flowsets = _fb_grid(topo, loads=(0.5,), seeds=(7, 8))
    before = engine.trace_count()
    for flows in flowsets:
        engine.run(topo, flows, cfg, n_ticks=2000)
    assert engine.trace_count() - before <= 1


@pytest.mark.slow
@pytest.mark.parametrize("scenario_name", [
    "fig5_load_sweep", "websearch_tail", "rack_local_skew"])
def test_conservation_across_registry_scenarios(scenario_name):
    """Packet conservation on every grid point of >= 3 registry scenarios:
    sent - delivered - queued - in-flight - pending-retx == 0 (exact at any
    tick; the retx term is empty at quiescence)."""
    sc = scenarios.get(scenario_name)
    # shrink: one load, one seed per scenario, both protocol groups
    from dataclasses import replace
    sc = replace(sc, loads=sc.loads[:1], seeds=sc.seeds[:1],
                 protos=sc.protos[:2])
    results = scenarios.run(sc, clos=CLOS, n_flows=50, drain=4000)
    assert len(results) == 2
    for r in results:
        st = r.state
        sent = int(np.asarray(st.sent).sum())
        delivered = int(np.asarray(st.delivered).sum())
        queued = int(np.asarray(st.f_cnt).sum())
        inflight = int((np.asarray(st.wire_f) >= 0).sum())
        retx_pending = int(np.asarray(st.retx_ring).sum())
        assert sent - delivered - queued - inflight - retx_pending == 0, \
            r.label
        assert (np.asarray(st.delivered) <= r.flows.size_pkts).all(), r.label
        done = np.asarray(st.done)
        assert (done >= 0).mean() > 0.9, f"{r.label}: too few completed"


@pytest.mark.parametrize("proto", [BFC, BFC_NO_BUFOPT],
                         ids=lambda p: p.name)
def test_batched_resume_gate_matches_each_lanes_own_run(proto):
    """The resume pop runs on a tick where ANY lane of the batch pops
    (`phases.ctx.lane_any`). A gate opened by one lane must change no other
    lane: every lane's final SimState, emits and active ticks equal its own
    unbatched run, with and without `resume_limit`."""
    clos = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
    topo = topology.build(clos)
    dims = TopoDims.of(topo)
    cfg = SimConfig(proto=proto, clos=clos)
    flowsets = [workload.generate(
                    topo, workload.WorkloadParams(
                        workload="fb_hadoop", load=0.8, seed=seed,
                        incast_load=0.2, incast_degree=6,
                        incast_total_kb=600), 24)
                for seed in (11, 12, 13)]
    n = max(f.n_flows for f in flowsets)
    n_ticks = 2048
    go = engine.compiled_runner(dims, cfg, n, n_ticks, batched=True,
                                segment=256)
    st_b, em_b, act_b = go(
        sweep.stack_operands(flowsets, engine.static_cfg(cfg), n),
        sweep.stack_topos([topo] * 3, engine.static_cfg(cfg), dims))
    # the lanes resume different flows and go quiescent at different ticks,
    # so the gate opens for some lanes and not others
    pops = np.asarray(st_b.pl_head).sum(axis=(1, 2))
    assert pops.min() > 0 and len(set(pops.tolist())) == 3, pops
    assert len(set(np.asarray(act_b).tolist())) > 1, act_b

    serial = engine.compiled_runner(dims, cfg, n, n_ticks, segment=256)
    for k, flows in enumerate(flowsets):
        st_s, em_s, act_s = serial(
            engine.pack_flows(sweep.pad_flowset(flows, n), cfg),
            pack_topo(topo, infinite_buffer=proto.infinite_buffer))
        assert int(act_b[k]) == int(act_s), f"lane {k} active ticks"
        assert np.array_equal(np.asarray(em_b[k]), np.asarray(em_s)), \
            f"lane {k} emits"
        for name in st_s._fields:
            assert np.array_equal(np.asarray(getattr(st_b, name)[k]),
                                  np.asarray(getattr(st_s, name))), \
                f"lane {k} SimState.{name}"


def test_padded_count_rounds_up(tiny_topo):
    flowsets = _fb_grid(tiny_topo, loads=(0.5,), seeds=(1,), n_flows=70)
    assert sweep.padded_count(flowsets, pad_multiple=64) == 128
    assert sweep.padded_count(flowsets, pad_multiple=1) == 70


# ---- trim_state / select_config at chunk boundaries -------------------------
# A budget-chunked run stitches (width)-lane chunks back into one batched
# SimState; lanes adjacent to a seam, the lone lane of a K=1 batch, and
# lanes of the lane-0-padded tail chunk must all trim/select identically to
# an unchunked or serial run.

def _serial_ref(topo, flows, cfg, n_ticks):
    st, em = engine.run(topo, flows, cfg, n_ticks)
    return sweep.trim_state(st, flows.n_flows), em


def _assert_lane_matches(st_b, em_b, k, topo, flows, cfg, n_ticks, label):
    st_ref, em_ref = _serial_ref(topo, flows, cfg, n_ticks)
    st_k = sweep.select_config(st_b, k, flows.n_flows)
    assert np.array_equal(em_b[k], em_ref), f"{label}: lane {k} emits"
    for name in st_ref._fields:
        assert np.array_equal(np.asarray(getattr(st_k, name)),
                              np.asarray(getattr(st_ref, name))), \
            f"{label}: lane {k} SimState.{name}"


def test_single_lane_batch_matches_serial(tiny_topo):
    """K=1: the degenerate batch (one lane, one chunk, no tail padding)
    still trims back to the serial run bit-for-bit."""
    cfg = SimConfig(proto=BFC, clos=CLOS)
    [flows] = _fb_grid(tiny_topo, loads=(0.5,), seeds=(9,), n_flows=30)
    n_ticks = int(flows.horizon + 800)
    st_b, em_b = sweep.run_batch(tiny_topo, [flows], cfg, n_ticks)
    assert em_b.shape[0] == 1
    _assert_lane_matches(st_b, em_b, 0, tiny_topo, flows, cfg, n_ticks,
                         "single-lane")


def test_select_config_on_chunk_seams_and_padded_tail(tiny_topo):
    """K=5 split into width-2 chunks: chunk boundaries fall after lanes 1
    and 3, and the tail chunk holds one real lane + one lane-0 repeat.
    Lanes on either side of a seam (1, 2) and the tail lane (4) must
    select/trim identically to their serial runs; the lane-0 pad must be
    dropped from the merged batch entirely."""
    cfg = SimConfig(proto=BFC, clos=CLOS)
    flowsets = _fb_grid(tiny_topo, loads=(0.5,), seeds=(1, 2, 3, 4, 5),
                        n_flows=24)
    n_ticks = int(max(f.horizon for f in flowsets) + 800)
    per = sweep.lane_state_bytes(topology.TopoDims.of(tiny_topo), cfg,
                                 sweep.padded_count(flowsets), n_ticks)
    st_b, em_b = sweep.run_batch(tiny_topo, flowsets, cfg, n_ticks,
                                 max_batch_bytes=4 * per)  # /depth 2 -> w=2
    # padded tail lane was dropped: exactly K lanes in the merged result
    assert em_b.shape[0] == 5
    assert np.asarray(st_b.done).shape[0] == 5
    for k in (1, 2, 4):
        _assert_lane_matches(st_b, em_b, k, tiny_topo, flowsets[k], cfg,
                             n_ticks, "seam/tail")


def test_tail_pad_is_lane0_repeat_before_trim(tiny_topo):
    """The tail chunk's pad lanes are repeats of lane 0 by contract; the
    merged result must NOT contain them, and lane 0 itself must be the
    chunk-0 copy (first occurrence), not the tail repeat."""
    cfg = SimConfig(proto=BFC, clos=CLOS)
    flowsets = _fb_grid(tiny_topo, loads=(0.5,), seeds=(1, 2, 3),
                        n_flows=24)
    n_ticks = int(max(f.horizon for f in flowsets) + 800)
    per = sweep.lane_state_bytes(topology.TopoDims.of(tiny_topo), cfg,
                                 sweep.padded_count(flowsets), n_ticks)
    st_b, em_b = sweep.run_batch(tiny_topo, flowsets, cfg, n_ticks,
                                 max_batch_bytes=4 * per)  # chunks: 2, 1+1pad
    assert em_b.shape[0] == 3
    # the pad lane reran lane 0's workload, so lane 0 selected from the
    # merged batch equals the serial lane-0 run (pad did not leak in)
    _assert_lane_matches(st_b, em_b, 0, tiny_topo, flowsets[0], cfg,
                         n_ticks, "lane0-vs-pad")
