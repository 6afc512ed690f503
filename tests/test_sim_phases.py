"""Per-phase unit tests for the decomposed simulator step.

Each phase module under repro.sim.phases is independently importable and
runs eagerly (no jit) on hand-crafted SimStates, so a single phase's
contract — resume pops, head-of-line dequeues, NIC eligibility, wire
delivery, feedback booking, histogram masking — is checkable in isolation
from the full scan."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.core import bloom
from repro.sim import engine, phases, sweep, topology, workload
from repro.sim.config import BFC, BFC_NO_BUFOPT, DCQCN, SimConfig
from repro.sim.topology import ClosParams, TopoDims, pack_topo

CLOS = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)

PHASE_MODULES = ["ctx", "control", "switch_tx", "nic_tx", "arrivals",
                 "feedback", "stats"]


def _setup(proto=BFC, n_flows=12, dims=None):
    topo = topology.build(CLOS)
    cfg = engine.static_cfg(SimConfig(proto=proto, clos=CLOS))
    flows = workload.generate(
        topo, workload.WorkloadParams(workload="uniform", load=0.5, seed=5),
        n_flows)
    dims = dims or TopoDims.of(topo)
    env = phases.make_env(dims, cfg, flows.n_flows)
    init_state, _ = engine.make_step(dims, cfg, flows.n_flows)
    ops = engine.pack_flows(flows, SimConfig(proto=proto, clos=CLOS))
    tops = pack_topo(topo, infinite_buffer=proto.infinite_buffer, dims=dims)
    return env, init_state(), ops, tops, topo, flows


def _through(env, st, ops, tops, upto):
    """Run the pipeline through phase `upto` (inclusive), eagerly."""
    pipeline = [phases.control, phases.switch_tx, phases.nic_tx,
                phases.arrivals, phases.feedback]
    ctx = phases.derive(env, st, ops, tops)
    for fn in pipeline[:upto]:
        ctx = fn(env, st, ops, tops, ctx)
    return ctx


def test_phase_modules_independently_importable():
    for name in PHASE_MODULES:
        mod = importlib.import_module(f"repro.sim.phases.{name}")
        assert mod.__doc__, name
        public = name if name != "ctx" else "derive"
        assert callable(getattr(mod, public)), name


def test_derive_initial_tick():
    env, st, ops, tops, topo, flows = _setup()
    ctx = phases.derive(env, st, ops, tops)
    assert np.asarray(ctx.occ).sum() == 0
    assert not np.asarray(ctx.qpaused).any()
    assert not np.asarray(ctx.pfc_paused).any()
    # empty queues: n_active clamps to 1, threshold = full pause window
    assert (np.asarray(ctx.th) == env.cfg.timing.pause_window).all()
    want = np.where(np.asarray(flows.arrival_tick) == 0,
                    np.asarray(flows.size_pkts), 0)
    assert np.array_equal(np.asarray(ctx.rem_src), want)


def test_derive_head_pause_bits_equal_the_element_gather():
    """Mid-run, with pause bits landed and queues paused, derive's dense
    head-of-queue test equals the per-element gather into each queue's own
    port row of the received snapshot."""
    topo = topology.build(CLOS)
    cfg = SimConfig(proto=BFC, clos=CLOS)
    flows = workload.generate(topo, workload.WorkloadParams(
        workload="uniform", load=0.9, incast_load=0.3, incast_degree=6,
        incast_total_kb=600, seed=5), 24)
    st, _ = engine.run(topo, flows, cfg, 300, early_exit=False)
    st = jax.tree.map(jnp.asarray, st)
    dims = TopoDims.of(topo)
    env = phases.make_env(dims, engine.static_cfg(cfg), flows.n_flows)
    ops = engine.pack_flows(flows, cfg)
    tops = pack_topo(topo, infinite_buffer=BFC.infinite_buffer, dims=dims)
    ctx = phases.derive(env, st, ops, tops)

    occ = np.asarray(st.qtail - st.qhead)
    head = np.take_along_axis(np.asarray(st.qbuf),
                              (np.asarray(st.qhead) % env.CAP)[..., None],
                              axis=2)[..., 0]
    head_pos = np.asarray(ops.fpos)[np.maximum(head >> 1, 0)]   # (P, Q, S)
    rx = np.asarray(st.bloom_rx)
    got = rx[np.arange(env.P)[:, None, None], np.arange(env.S), head_pos]
    want = got.all(axis=-1) & (occ > 0)
    assert rx.any() and want.any() and (occ > 0).sum() > want.sum()
    assert np.array_equal(np.asarray(ctx.qpaused), want)


def _pending_resume(st, ops, flows):
    """A state whose resume ring holds one paused, below-threshold flow."""
    routes = np.asarray(flows.routes)
    f = int(np.argmax((routes >= 0).sum(1) >= 2))  # any multi-hop flow
    hop, p = 1, int(routes[f, 1])
    up = int(routes[f, 0])
    counts = bloom.add_batch(st.bloom_counts, jnp.asarray([up]),
                             ops.fpos[f][None], jnp.asarray([1]))
    st = st._replace(
        f_paused=st.f_paused.at[f, hop].set(True),
        f_q=st.f_q.at[f, hop].set(0),
        f_cnt=st.f_cnt.at[f, hop].set(1),
        pl=st.pl.at[p, 0, 0].set(f),
        pl_tail=st.pl_tail.at[p, 0].set(1),
        bloom_counts=counts)
    return st, f, hop, p


def test_control_pops_resume_ring_at_tau():
    env, st, ops, tops, topo, flows = _setup()
    st, f, hop, p = _pending_resume(st, ops, flows)
    ctx = _through(env, st, ops, tops, upto=1)   # t=0 is a tau boundary
    assert not bool(np.asarray(ctx.f_paused)[f, hop])
    assert int(np.asarray(ctx.pl_head)[p, 0]) == 1
    assert int(np.asarray(ctx.bloom_counts).sum()) == 0  # filter cleaned


def _pending_resumes(st, ops, flows, q_of):
    """A state whose resume rings hold every multi-hop flow, paused at its
    second hop in queue `q_of(f)` of that port."""
    routes = np.asarray(flows.routes)
    fs = [f for f in range(len(routes)) if (routes[f] >= 0).sum() >= 2]
    f_ar = jnp.asarray(fs)
    p_ar = jnp.asarray(routes[fs, 1])
    q_ar = jnp.asarray([q_of(f) for f in fs])
    counts = bloom.add_batch(st.bloom_counts, jnp.asarray(routes[fs, 0]),
                             ops.fpos[f_ar], jnp.ones(len(fs), jnp.int32))
    st = st._replace(
        f_paused=st.f_paused.at[f_ar, 1].set(True),
        f_q=st.f_q.at[f_ar, 1].set(q_ar),
        f_cnt=st.f_cnt.at[f_ar, 1].set(1),
        pl=st.pl.at[p_ar, q_ar, 0].set(f_ar),
        pl_tail=st.pl_tail.at[p_ar, q_ar].set(1),
        bloom_counts=counts)
    return st, fs


@pytest.mark.parametrize("chunk", [1, 3])
def test_control_pops_every_queue_whatever_the_chunk(monkeypatch, chunk):
    """With more popping queues than one pass takes, the passes pop them
    all, and the result is the single pass's bit for bit."""
    env, st, ops, tops, topo, flows = _setup()
    st, fs = _pending_resumes(st, ops, flows, lambda f: f % env.Q)
    assert len(fs) > chunk
    whole = _through(env, st, ops, tops, upto=1)
    monkeypatch.setattr(importlib.import_module("repro.sim.phases.control"),
                        "RESUME_CHUNK", chunk)
    ctx = _through(env, st, ops, tops, upto=1)
    for name in ("pl_head", "f_paused", "bloom_counts"):
        assert np.array_equal(np.asarray(getattr(ctx, name)),
                              np.asarray(getattr(whole, name))), name
    assert not np.asarray(ctx.f_paused)[fs, 1].any()
    assert int(np.asarray(ctx.bloom_counts).sum()) == 0


def test_control_skips_resume_pop_between_tau_boundaries():
    """Off a tau boundary no queue may pop (`resume_limit`), so the resume
    block is skipped and leaves its state untouched bit for bit."""
    env, st, ops, tops, topo, flows = _setup()
    st, f, hop, p = _pending_resume(st, ops, flows)
    st = st._replace(t=jnp.int32(1))
    assert env.TAU > 1
    ctx = _through(env, st, ops, tops, upto=1)
    for name in ("pl_head", "f_paused", "bloom_counts"):
        assert np.array_equal(np.asarray(getattr(ctx, name)),
                              np.asarray(getattr(st, name))), name
    assert bool(np.asarray(ctx.f_paused)[f, hop])


def _eqns(jaxpr, in_cond=False):
    """(eqn, inside a cond branch) for every equation of `jaxpr`, nested
    ones included."""
    for e in jaxpr.eqns:
        yield e, in_cond
        inner = in_cond or e.primitive.name == "cond"
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


@pytest.mark.parametrize("proto,gated", [(BFC, True), (BFC_NO_BUFOPT, True),
                                         (DCQCN, False)],
                         ids=["bfc", "bfc_nobufopt", "dcqcn"])
def test_batched_runner_keeps_resume_pop_under_cond(proto, gated):
    """In the batched (vmapped) runner the resume gathers and scatters lie
    inside a real `cond`: their predicate is reduced over the lanes, so vmap
    does not turn the conditional into a select that runs them every tick.
    A protocol without backpressure traces no resume block at all."""
    topo = topology.build(CLOS)
    dims = TopoDims.of(topo)
    cfg = SimConfig(proto=proto, clos=CLOS)
    flowsets = [workload.generate(
        topo, workload.WorkloadParams(workload="uniform", load=0.5,
                                      seed=seed), 12) for seed in (1, 2)]
    go = engine.compiled_runner(dims, cfg, 12, 512, batched=True)
    scfg = engine.static_cfg(cfg)
    jaxpr = jax.make_jaxpr(go)(
        sweep.stack_operands(flowsets, scfg, 12),
        sweep.stack_topos([topo] * 2, scfg, dims)).jaxpr
    resume = [(e.primitive.name, inside) for e, inside in _eqns(jaxpr)
              if "control.resume" in str(e.source_info.name_stack)]
    if gated:
        assert resume and all(inside for _, inside in resume), \
            "resume ops must all lie inside a cond branch"
        assert "scatter" in {name for name, _ in resume}
    else:
        assert not resume


def test_switch_tx_dequeues_head_and_releases_queue():
    env, st, ops, tops, topo, flows = _setup()
    routes = np.asarray(flows.routes)
    f = int(np.argmax((routes >= 0).sum(1) >= 2))
    hop, p, q = 1, int(routes[f, 1]), 3
    st = st._replace(
        qbuf=st.qbuf.at[p, q, 0].set(f * 2),
        qtail=st.qtail.at[p, q].set(1),
        f_cnt=st.f_cnt.at[f, hop].set(1),
        f_q=st.f_q.at[f, hop].set(q))
    ctx = _through(env, st, ops, tops, upto=2)
    assert bool(np.asarray(ctx.can_tx)[p])
    assert int(np.asarray(ctx.tx_entry)[p]) == f * 2
    assert int(np.asarray(ctx.qhead)[p, q]) == 1
    # last packet left: flow departs the hop and frees its queue slot
    assert int(np.asarray(ctx.f_cnt)[f, hop]) == 0
    assert int(np.asarray(ctx.f_q)[f, hop]) == -1


def test_nic_tx_transmits_one_packet_per_busy_server():
    env, st, ops, tops, topo, flows = _setup()
    ctx = _through(env, st, ops, tops, upto=3)
    pre = phases.derive(env, st, ops, tops).rem_src
    n_tx = int(np.asarray(ctx.nic_tx).sum())
    busy = len({int(s) for s, a in zip(np.asarray(flows.src),
                                       np.asarray(flows.arrival_tick))
                if a == 0})
    assert n_tx == busy                       # one packet per active server
    assert int(np.asarray(pre).sum() - np.asarray(ctx.rem_src).sum()) == n_tx
    assert int(np.asarray(ctx.sent).sum()) == n_tx


def test_arrivals_delivers_and_schedules_ack():
    env, st, ops, tops, topo, flows = _setup()
    routes = np.asarray(flows.routes)
    f = int(np.argmax((routes >= 0).sum(1) == 2))  # intra-rack: 2 hops
    last_hop = 1
    last_port = int(routes[f, last_hop])
    st = st._replace(wire_f=st.wire_f.at[last_port, 0].set(f * 2),
                     wire_hop=st.wire_hop.at[last_port, 0].set(last_hop))
    ctx = _through(env, st, ops, tops, upto=4)
    assert int(np.asarray(ctx.delivered)[f]) == 1
    # feedback delay is derived in-trace: hops * traced prop_ticks + 1
    fb = (int(np.asarray(ops.hops)[f]) * CLOS.prop_ticks + 1) % env.RING
    assert int(np.asarray(ctx.ack_ring)[fb, f]) == 1


def test_feedback_books_due_acks():
    env, st, ops, tops, topo, flows = _setup()
    st = st._replace(ack_ring=st.ack_ring.at[0, 0].add(2))  # due at t=0
    ctx = _through(env, st, ops, tops, upto=5)
    assert int(np.asarray(ctx.acked)[0]) == 2
    assert int(np.asarray(ctx.ack_ring)[0, 0]) == 0         # row drained


def test_stats_assembles_next_state_and_emit():
    env, st, ops, tops, topo, flows = _setup()
    ctx = _through(env, st, ops, tops, upto=5)
    new_st, emit = phases.stats(env, st, ops, tops, ctx)
    assert int(new_st.t) == 1
    assert emit.shape == (3,)
    # t=0 is a sample tick: one histogram count per (real) switch
    assert int(np.asarray(new_st.occ_hist).sum()) == topo.n_switches


def test_stats_masks_phantom_ports_and_switches():
    dims = TopoDims(n_ports=CLOS.n_servers + 2 * 12 + 2 * 2 + 7,
                    n_servers=CLOS.n_servers + 3,
                    n_switches=6, prop_max=CLOS.prop_ticks, hops=4)
    env, st, ops, tops, topo, flows = _setup(dims=dims)
    ctx = _through(env, st, ops, tops, upto=5)
    new_st, _ = phases.stats(env, st, ops, tops, ctx)
    real_sw_ports = topo.n_ports - topo.params.n_servers
    assert int(np.asarray(new_st.occ_hist).sum()) == topo.n_switches
    assert int(np.asarray(new_st.flows_hist).sum()) == real_sw_ports
