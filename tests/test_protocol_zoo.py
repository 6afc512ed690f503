"""Protocol-zoo behavioural properties: FairQ fairness, oracle optimality.

Three law-level contracts the zoo's new families must satisfy beyond
bit-identity across execution modes:

* **FairQ** (arXiv 2401.04850): NIC rates stay within link capacity and,
  on hand-checked single-bottleneck fabrics, converge to the max-min fair
  share (1/n of the bottleneck for n competing flows).
* **Oracle work conservation** (arXiv 1710.02548): with per-flow queues,
  infinite buffer, and no pause machinery, every backlogged switch port
  transmits every tick — verified per tick against the simulator state,
  not the scheduler's own claim.
* **Oracle optimality**: the centralized scheduler's FCT tail
  lower-bounds every realizable family on the identical workload — the
  property that makes `metrics.distance_from_optimal` meaningful.

Hypothesis drives the rate-bound search when installed; a seeded-rng
sweep of the same property always runs (the repo's test_rank_layout.py
convention). The table1-style differential ordering run is slow-marked."""
import numpy as np
import pytest

pytestmark = pytest.mark.tier1

import jax
import jax.numpy as jnp

from repro.sim import engine, topology, workload
from repro.sim.config import (BFC, DCQCN, DCTCP, FAIRQ, ORACLE, SFC,
                              SimConfig)
from repro.sim.topology import ClosParams, TopoDims, ideal_fct_ticks
from repro.sim.trace import EMIT_BASE, TraceSpec, layout
from repro.sim.workload import FlowSet

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CLOS = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
N_FLOWS = 24


@pytest.fixture(scope="module")
def topo():
    return topology.build(CLOS)


def _flows(topo, seed, load=0.5, incast=0.0):
    wp = workload.WorkloadParams(workload="uniform", load=load, seed=seed,
                                 incast_load=incast, incast_degree=6,
                                 incast_total_kb=768)
    return workload.generate(topo, wp, n_flows=N_FLOWS)


def _incast_flowset(topo, n: int, size_pkts: int = 1 << 20) -> FlowSet:
    """Hand-built n-to-1 fabric: servers 1..n each send one long flow to
    server 0 from tick 0, so the one bottleneck (ToR egress to server 0)
    carries exactly n flows and the max-min fair share is 1/n."""
    src = np.arange(1, n + 1, dtype=np.int32)
    dst = np.zeros(n, np.int32)
    sizes = np.full(n, size_pkts, np.int32)
    fid = np.arange(1, n + 1, dtype=np.int32)
    routes = topo.routes(src, dst, fid)
    return FlowSet(src=src, dst=dst, size_pkts=sizes,
                   arrival_tick=np.zeros(n, np.int32), routes=routes,
                   ideal_fct=ideal_fct_ticks(
                       routes, sizes.astype(np.int64),
                       topo.params.prop_ticks).astype(np.int32),
                   fid=fid,
                   is_incast=np.zeros(n, bool), horizon=1)


# ---- FairQ: rates within capacity -------------------------------------------

def _assert_fairq_rates_bounded(topo, seed, load):
    cfg = SimConfig(proto=FAIRQ, clos=CLOS)
    flows = _flows(topo, seed, load)
    st, _ = engine.run(topo, flows, cfg, int(flows.horizon + 2500))
    rate = np.asarray(st.rate)
    assert (rate >= FAIRQ.fairq_rate_min - 1e-9).all()
    assert (rate <= 1.0 + 1e-6).all(), "rate above link capacity"
    assert (np.asarray(st.tokens) <= 2.0 + 1e-6).all()
    assert (np.asarray(st.done) >= 0).all(), "FairQ starved a flow"


def test_fairq_rates_bounded_seeded_sweep(topo):
    for seed in (3, 11, 29):
        _assert_fairq_rates_bounded(topo, seed, 0.5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**16),
           load=st.floats(min_value=0.3, max_value=0.7))
    def test_fairq_rates_bounded_hypothesis(seed, load):
        _assert_fairq_rates_bounded(topology.build(CLOS), seed, load)


@pytest.mark.parametrize("n", [2, 6])
def test_fairq_converges_to_max_min_share(topo, n):
    """n long-lived flows into one server: every rate settles at the
    max-min share 1/n, and the bottleneck is not oversubscribed."""
    cfg = SimConfig(proto=FAIRQ, clos=CLOS)
    flows = _incast_flowset(topo, n)
    st, _ = engine.run(topo, flows, cfg, 2500)
    rate = np.asarray(st.rate)
    assert (np.asarray(st.done) < 0).all(), "long flows must outlive the run"
    assert np.allclose(rate, 1.0 / n, atol=0.02), rate
    assert rate.sum() <= 1.0 + 0.05, "bottleneck oversubscribed"


# ---- oracle: work conservation ----------------------------------------------

def test_oracle_work_conserving(topo):
    """Every switch egress port with backlog at tick start transmits that
    tick (no pause machinery, per-flow queues, infinite buffer): checked
    per tick from the state's queue counters against the traced per-port
    switch decision, over a horizon that includes an incast burst."""
    spec = TraceSpec(kernel_path=True)
    cfg = SimConfig(proto=ORACLE, clos=CLOS, trace=spec)
    flows = _flows(topo, seed=11, incast=0.15)
    dims = TopoDims.of(topo)
    lay = layout(spec, dims.n_ports, dims.n_switches)
    can_sl = lay.slice_of("can_tx")
    init_state, step = engine.make_step(dims, engine.static_cfg(cfg),
                                        flows.n_flows)
    step = jax.jit(step)
    ops = engine.pack_flows(flows, cfg)
    tp = topology.pack_topo(topo,
                            infinite_buffer=cfg.proto.infinite_buffer)
    sw_port = ~np.asarray(tp.port_is_nic) & np.asarray(tp.port_valid)
    st = init_state()
    saw_backlog = 0
    for t in range(int(flows.horizon + 1600)):   # covers the incast drain
        occ_p = np.asarray(st.qtail - st.qhead).sum(axis=1)
        st, emit = step(st, ops, tp)
        can_tx = np.asarray(emit)[EMIT_BASE:][can_sl].astype(bool)
        backlog = (occ_p > 0) & sw_port
        saw_backlog += int(backlog.sum())
        idle = backlog & ~can_tx
        assert not idle.any(), \
            f"tick {t}: backlogged ports {np.nonzero(idle)[0]} idle"
    assert saw_backlog > 0, "horizon never exercised a backlogged port"
    assert (np.asarray(st.done) >= 0).all()


# ---- oracle: FCT lower bound ------------------------------------------------

def _p99_slowdown(st, flows) -> float:
    done = np.asarray(st.done)
    mask = (done >= 0) & ~flows.is_incast
    slow = ((done - flows.arrival_tick).astype(np.float64)
            / np.maximum(flows.ideal_fct, 1))[mask]
    return float(np.percentile(slow, 99))


def test_oracle_lower_bounds_every_family(topo):
    """On one fixed workload (uniform + incast burst), the centralized
    scheduler's p99 FCT slowdown is <= every realizable family's — the
    invariant distance_from_optimal >= 1.0 rests on."""
    flows = _flows(topo, seed=11, incast=0.15)
    n_ticks = int(flows.horizon + 2500)
    tails = {}
    for proto in (ORACLE, BFC, DCTCP, DCQCN, SFC, FAIRQ):
        st, _ = engine.run(topo, flows, SimConfig(proto=proto, clos=CLOS),
                           n_ticks)
        done = np.asarray(st.done)
        assert (done >= 0).all(), f"{proto.name}: incomplete flows"
        tails[proto.name] = _p99_slowdown(st, flows)
    for name, p99 in tails.items():
        assert tails["oracle"] <= p99 + 1e-9, \
            f"oracle p99 {tails['oracle']:.3f} > {name} {p99:.3f}"


# ---- differential ordering (table1-style, slow) -----------------------------

@pytest.mark.slow
def test_differential_ordering_short_flows():
    """The paper's headline ordering on a bigger grid: BFC's short-flow
    tail beats the end-to-end CC schemes (DCQCN, DCTCP), and the oracle
    lower-bounds everything — overall AND in the short-flow bin."""
    clos = ClosParams(n_servers=16, n_tor=2, n_spine=2,
                      switch_buffer_pkts=2048)
    topo = topology.build(clos)
    wp = workload.WorkloadParams(workload="websearch", load=0.6, seed=42)
    flows = workload.generate(topo, wp, n_flows=128)
    n_ticks = int(flows.horizon + 30000)
    short = flows.size_pkts <= 100          # <=100 KB bin
    assert short.sum() >= 20
    p99 = {}
    p99_short = {}
    for proto in (BFC, DCTCP, DCQCN, ORACLE):
        st, _ = engine.run(topo, flows, SimConfig(proto=proto, clos=clos),
                           n_ticks)
        done = np.asarray(st.done)
        assert (done >= 0).all(), f"{proto.name}: incomplete flows"
        slow = ((done - flows.arrival_tick).astype(np.float64)
                / np.maximum(flows.ideal_fct, 1))
        p99[proto.name] = float(np.percentile(slow, 99))
        p99_short[proto.name] = float(np.percentile(slow[short], 99))
    assert p99_short["bfc"] <= p99_short["dcqcn"] + 1e-9
    assert p99_short["bfc"] <= p99_short["dctcp"] + 1e-9
    for name in ("bfc", "dctcp", "dcqcn"):
        assert p99["oracle"] <= p99[name] + 1e-9
        assert p99_short["oracle"] <= p99_short[name] + 1e-9
