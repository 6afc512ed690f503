"""Tick-synchronous, fully vectorized packet-level network simulator.

One XLA program steps the whole network: every egress port transmits at
most one MTU packet per tick, packets propagate on "wires" with a fixed
tick delay, switches run the configured protocol (BFC / PFC / DCTCP /
DCQCN / HPCC / Ideal-FQ and the paper's ablations).

The runner is **active-horizon aware**: scenario horizons are padded with
a long drain tail (`n_ticks` = max horizon + drain), and most of that tail
simulates an empty network. Instead of one flat `lax.scan(n_ticks)`, the
compiled program runs a `lax.while_loop` over fixed-width tick segments
(`DEFAULT_SEGMENT`, a static knob): after each segment a batch-wide
`quiescent` predicate decides whether anything can still change, emits
land in a preallocated (T, 3 + trace channels) buffer via dynamic
slices (`SimConfig.trace` selects the opt-in channels; off = width 3,
see `sim/trace/`), and the skipped
quiescent suffix is reconstructed in closed form (`_finish_tail`) — the
final state and emits are bit-identical to the flat scan, which survives
as the `early_exit=False` escape hatch for A/B runs. The runner returns
`(state, emits, active_ticks)`; `active_ticks` (< n_ticks on early exit)
is landed by the exec layer into `exec.last_active_ticks()`.

This module owns the operand/state definitions and the compile cache; the
per-tick work lives in the phase pipeline under `repro.sim.phases`
(derive -> control -> switch_tx -> nic_tx -> arrivals -> feedback -> stats).
See docs/ARCHITECTURE.md for the full design: the phase pipeline, the two
traced operand bundles (`FlowOperands` here, `topology.TopoOperands`), and
both padding contracts (phantom flows, phantom ports/switches/servers) that
let `sim/sweep.py` vmap a whole topology x workload x seed grid through one
compiled program. Only `TopoDims` (port/server/switch counts, padded
wire-ring length `prop_max`, route width `hops`) and the protocol/timing
configuration remain compile-time constants; the link propagation delay
itself is the traced `TopoOperands.prop_ticks` modulus, so mixed-latency
grids share a program.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ..core import bloom
from ..core.flow_table import FlowTableParams, buckets_of
from ..kernels.bfc_step import ops as kernel_ops
from . import phases
from .config import SimConfig
from .phases import BIG, I32  # noqa: F401  (re-export for callers/tests)
from .topology import TopoDims, Topology, pack_topo
from .trace import EMIT_BASE
from .trace import layout as trace_layout

# Arrival tick of padded "phantom" flows (sweep batching): beyond any
# simulated horizon, so they never start, never transmit, never allocate.
PHANTOM_ARRIVAL = int(1 << 30)

# Ticks per while-loop segment of the active-horizon runner: the quiescence
# check runs once per segment, so a run overshoots the true quiescent point
# by < one segment. Static (part of the compile-cache key) — every caller
# must agree on it for the one-compilation-per-protocol contract to hold.
DEFAULT_SEGMENT = 512

# Name of the vmapped lane axis of a batched runner, over which a per-tick
# conditional reduces its predicate (`phases.ctx.lane_any`). Distinct from
# the ``lanes`` mesh axis that `shard_map` splits the batch over.
LANE_AXIS = "lane"


class FlowOperands(NamedTuple):
    """Per-flow metadata fed to the jitted step as traced operands.

    Shapes are static per compiled program: (F,) / (F, H) / (F, S), with
    H = `TopoDims.hops`, the route width (shorter routes padded with -1).
    `sim/sweep.py` stacks these along a leading batch axis and vmaps the
    step, so one compilation serves a whole seed/load grid. Routes name
    egress ports of the lane's own fabric, so the per-flow routing table
    doubles as the topology's routing operand."""
    routes: jnp.ndarray      # (F, H) egress port per hop, -1 padded
    src: jnp.ndarray         # (F,) source server
    dst: jnp.ndarray         # (F,) destination server
    size: jnp.ndarray        # (F,) flow size in packets
    arrival: jnp.ndarray     # (F,) arrival tick (PHANTOM_ARRIVAL = never)
    fid: jnp.ndarray         # (F,) 32-bit flow id
    fpos: jnp.ndarray        # (F, S) Bloom-filter bit positions
    fbucket: jnp.ndarray     # (F,) flow-table bucket
    hops: jnp.ndarray        # (F,) route hop count (transmissions per pkt)


def pack_flows(flows, cfg: SimConfig) -> FlowOperands:
    """Derive the traced operand bundle for a FlowSet under `cfg`.

    Deliberately independent of `cfg.clos`: the one-way feedback delay is
    derived in-trace as `hops * TopoOperands.prop_ticks + 1`, so one packed
    bundle is correct on any fabric — including mixed-latency batches where
    each lane carries its own traced propagation delay."""
    bparams = bloom.BloomParams(cfg.bloom_stages, cfg.bloom_stage_bits)
    ftp = FlowTableParams(cfg.ft_buckets, cfg.ft_bucket_size)
    routes = np.asarray(flows.routes, np.int32)
    fid = jnp.asarray(np.asarray(flows.fid, np.int32))
    hops = (routes >= 0).sum(1).astype(np.int32)
    return FlowOperands(
        routes=jnp.asarray(routes),
        src=jnp.asarray(np.asarray(flows.src, np.int32)),
        dst=jnp.asarray(np.asarray(flows.dst, np.int32)),
        size=jnp.asarray(np.asarray(flows.size_pkts, np.int32)),
        arrival=jnp.asarray(np.asarray(flows.arrival_tick, np.int32)),
        fid=fid,
        fpos=bloom.positions(fid, bparams),
        fbucket=buckets_of(fid, ftp),
        hops=jnp.asarray(hops))


class SimState(NamedTuple):
    t: jnp.ndarray
    # flow / source state
    rem_src: jnp.ndarray      # (F,) pkts not yet transmitted by the NIC
    sent: jnp.ndarray         # (F,)
    acked: jnp.ndarray        # (F,)
    delivered: jnp.ndarray    # (F,)
    done: jnp.ndarray         # (F,) completion tick or -1
    cwnd: jnp.ndarray         # (F,) f32
    cwnd_ref: jnp.ndarray     # (F,) f32 (HPCC reference window)
    rate: jnp.ndarray         # (F,) f32 pkts/tick (DCQCN)
    rate_target: jnp.ndarray  # (F,) f32
    tokens: jnp.ndarray       # (F,) f32
    alpha: jnp.ndarray        # (F,) f32
    ack_seen: jnp.ndarray     # (F,) acks in current epoch
    mark_seen: jnp.ndarray    # (F,)
    cc_timer: jnp.ndarray     # (F,) epoch countdown
    since_dec: jnp.ndarray    # (F,) ticks since last rate decrease
    # queues
    qbuf: jnp.ndarray         # (P, Q, CAP) packed entry = f*2+mark, -1 empty
    qhead: jnp.ndarray        # (P, Q)
    qtail: jnp.ndarray        # (P, Q)
    qptr: jnp.ndarray         # (P,) DRR pointer
    qsrf: jnp.ndarray         # (P, Q) SRF priority key
    # per-flow per-hop switch state (the flow hash table contents)
    f_q: jnp.ndarray          # (F, H) assigned queue or -1
    f_cnt: jnp.ndarray        # (F, H) packets queued at that hop
    f_paused: jnp.ndarray     # (F, H) bool
    # dest-keyed assignment (BFC+DestFQ)
    d_q: jnp.ndarray          # (P, NDST)
    d_cnt: jnp.ndarray        # (P, NDST)
    # backpressure signalling
    bloom_counts: jnp.ndarray  # (P, S, B) counting filter (at downstream)
    bloom_mid: jnp.ndarray     # (P, S, B) bool snapshot in flight
    bloom_rx: jnp.ndarray      # (P, S, B) bool snapshot applied at upstream
    pl: jnp.ndarray            # (P, Q, PLCAP) to-be-resumed flow ring
    pl_head: jnp.ndarray       # (P, Q)
    pl_tail: jnp.ndarray       # (P, Q)
    # PFC
    ing_occ: jnp.ndarray       # (P,) pkts at downstream that arrived via port
    pfc_paused: jnp.ndarray    # (P,) bool
    # links (rings wrap at the lane's traced prop_ticks <= PROP_MAX)
    wire_f: jnp.ndarray        # (P, PROP_MAX) packed entries in flight
    wire_hop: jnp.ndarray      # (P, PROP_MAX)
    tx_ewma: jnp.ndarray       # (P,) f32 utilization estimate
    # feedback rings
    ack_ring: jnp.ndarray      # (RING, F) i32
    mark_ring: jnp.ndarray     # (RING, F) i32
    u_ring: jnp.ndarray        # (RING, F) f32 (HPCC max path util /
    #                            FairQ bottleneck flow count)
    retx_ring: jnp.ndarray     # (RRING, F) i32 (delayed retransmit credits)
    # SFC source signalling (inert zeros unless proto.source_signal)
    sfc_ring: jnp.ndarray      # (RING, F) i32 in-flight pause signals
    sfc_until: jnp.ndarray     # (F,) source paused until this tick
    # NIC scheduling
    nic_ptr: jnp.ndarray       # (NSRV,)
    # flow hash table occupancy model
    bucket_cnt: jnp.ndarray    # (NSW, NBUCKETS)
    # statistics accumulators
    stat_drops: jnp.ndarray
    stat_collisions: jnp.ndarray   # allocations that had to share a queue
    stat_allocs: jnp.ndarray
    stat_overflow: jnp.ndarray     # hash-table bucket overflows
    stat_pauses: jnp.ndarray       # pause events sent
    stat_pfc_ticks: jnp.ndarray    # (link,tick) pairs paused by PFC
    occ_hist: jnp.ndarray          # (BINS,) switch-occupancy histogram
    flows_hist: jnp.ndarray        # (FBINS,) active-flows-per-port histogram
    qlen_hist: jnp.ndarray         # (BINS,) physical queue length histogram


def make_step(dims: TopoDims, cfg: SimConfig, n_flows: int,
              lane_axis: str | None = None):
    """Build (init_state, step) for one static program signature.

    Only `dims` (topology shapes) and the protocol/timing config shape the
    program; per-flow metadata (`FlowOperands`) AND per-fabric tables
    (`TopoOperands`) arrive at trace time as operands of `step`, so one
    compiled program serves every workload on every same-shaped fabric.
    `cfg.clos` is deliberately unused here — strip it from cache keys.
    `lane_axis` names the vmap axis when the step runs batched."""
    pc, tm = cfg.proto, cfg.timing
    env = phases.make_env(dims, cfg, n_flows, lane_axis)
    P, NSRV, NSW, PROP = env.P, env.NSRV, env.NSW, env.PROP_MAX
    Q, CAP, PLCAP, S = env.Q, env.CAP, env.PLCAP, env.S
    F, H, RING, RRING = env.F, env.H, env.RING, env.RRING

    def init_state() -> SimState:
        z = functools.partial(jnp.zeros, dtype=I32)
        return SimState(
            t=jnp.int32(0),
            rem_src=z((F,)), sent=z((F,)), acked=z((F,)), delivered=z((F,)),
            done=jnp.full((F,), -1, I32),
            cwnd=jnp.full((F,), pc.window_init, jnp.float32),
            cwnd_ref=jnp.full((F,), pc.window_init, jnp.float32),
            rate=jnp.ones((F,), jnp.float32),
            rate_target=jnp.ones((F,), jnp.float32),
            tokens=jnp.ones((F,), jnp.float32),
            alpha=jnp.zeros((F,), jnp.float32),
            ack_seen=z((F,)), mark_seen=z((F,)),
            cc_timer=jnp.full((F,), tm.e2e_rtt_ticks, I32),
            since_dec=z((F,)),
            qbuf=jnp.full((P, Q, CAP), -1, I32),
            qhead=z((P, Q)), qtail=z((P, Q)), qptr=z((P,)),
            qsrf=jnp.full((P, Q), BIG, I32),
            f_q=jnp.full((F, H), -1, I32), f_cnt=z((F, H)),
            f_paused=jnp.zeros((F, H), bool),
            d_q=jnp.full((P, NSRV), -1, I32), d_cnt=z((P, NSRV)),
            bloom_counts=bloom.empty_counts(env.bparams, P),
            bloom_mid=jnp.zeros((P, S, env.bparams.stage_bits), bool),
            bloom_rx=jnp.zeros((P, S, env.bparams.stage_bits), bool),
            pl=jnp.full((P, Q, PLCAP), -1, I32), pl_head=z((P, Q)),
            pl_tail=z((P, Q)),
            ing_occ=z((P,)), pfc_paused=jnp.zeros((P,), bool),
            wire_f=jnp.full((P, PROP), -1, I32),
            wire_hop=jnp.zeros((P, PROP), I32),
            tx_ewma=jnp.zeros((P,), jnp.float32),
            ack_ring=z((RING, F)), mark_ring=z((RING, F)),
            u_ring=jnp.zeros((RING, F), jnp.float32),
            retx_ring=z((RRING, F)),
            sfc_ring=z((RING, F)), sfc_until=z((F,)),
            nic_ptr=z((NSRV,)),
            bucket_cnt=z((NSW, cfg.ft_buckets)),
            stat_drops=jnp.int32(0), stat_collisions=jnp.int32(0),
            stat_allocs=jnp.int32(0), stat_overflow=jnp.int32(0),
            stat_pauses=jnp.int32(0), stat_pfc_ticks=jnp.int32(0),
            occ_hist=z((cfg.occ_bins,)), flows_hist=z((cfg.flows_bins,)),
            qlen_hist=z((cfg.occ_bins,)),
        )

    def step(st: SimState, ops: FlowOperands, topo_ops):
        # `phase.<name>` lands in each op's op_name metadata, so a profiler
        # trace assigns device time to phases (docs/ARCHITECTURE.md,
        # "Observability"); it changes no instruction
        with jax.named_scope("phase.derive"):
            ctx = phases.derive(env, st, ops, topo_ops)
        with jax.named_scope("phase.control"):
            ctx = phases.control(env, st, ops, topo_ops, ctx)
        with jax.named_scope("phase.switch_tx"):
            ctx = phases.switch_tx(env, st, ops, topo_ops, ctx)
        with jax.named_scope("phase.nic_tx"):
            ctx = phases.nic_tx(env, st, ops, topo_ops, ctx)
        with jax.named_scope("phase.arrivals"):
            ctx = phases.arrivals(env, st, ops, topo_ops, ctx)
        with jax.named_scope("phase.feedback"):
            ctx = phases.feedback(env, st, ops, topo_ops, ctx)
        with jax.named_scope("phase.stats"):
            return phases.stats(env, st, ops, topo_ops, ctx)

    return init_state, step


# One entry appended per XLA trace of a simulator program (tracing happens
# exactly once per compilation), so tests and sweep drivers can assert how
# many compilations a grid actually triggered.
TRACE_EVENTS: list = []


def trace_count() -> int:
    return len(TRACE_EVENTS)


def static_cfg(cfg: SimConfig) -> SimConfig:
    """The compile-cache view of a SimConfig: `clos` stripped, because the
    topology is a traced operand — fabrics that differ only in ClosParams
    (and agree on `TopoDims`) share one executable — and
    `proto.kernel_impl` resolved to the concrete switch-decision path
    ('lax' | 'pallas' | 'interpret': REPRO_KERNEL env override applied,
    'auto' resolved per `kernels.bfc_step.ops`), so the cache is keyed on
    the program actually built."""
    impl = kernel_ops.resolve_impl(cfg.proto.kernel_impl, lax_name="lax")
    proto = (cfg.proto if impl == cfg.proto.kernel_impl
             else replace(cfg.proto, kernel_impl=impl))
    return replace(cfg, clos=None, proto=proto)


def quiescent(st: SimState, ops: FlowOperands) -> jnp.ndarray:
    """True iff no future tick can change anything but the closed-form
    leaves `_finish_tail` reconstructs (time, histogram zero-bins, the
    constant emit row, and the CC/decay replay).

    The predicate is deliberately total: every flow that will ever arrive
    has completed, nothing is in flight on wires or queues, every delayed
    feedback / retransmit credit has landed, and every backpressure signal
    (pause bits, Bloom pipeline, resume rings, PFC) has fully drained. Any
    weaker condition would let the skipped tail diverge from the flat
    scan."""
    flows_done = jnp.all((st.done >= 0) | (ops.arrival >= PHANTOM_ARRIVAL))
    net_empty = (jnp.all(st.wire_f < 0)
                 & jnp.all(st.qtail == st.qhead)
                 & jnp.all(st.f_cnt == 0)
                 & jnp.all(st.ack_ring == 0)
                 & jnp.all(st.mark_ring == 0)
                 & jnp.all(st.u_ring == 0.0)
                 & jnp.all(st.retx_ring == 0)
                 & jnp.all(st.sfc_ring == 0))
    # (st.sfc_until needs no clause: with every flow done and the signal
    # ring drained, a stale pause deadline can never gate anything again,
    # and the tail replay leaves it untouched -- exactly like the flat scan)
    signals_clear = (jnp.all(st.pl_tail == st.pl_head)
                     & jnp.all(st.bloom_counts == 0)
                     & ~jnp.any(st.bloom_mid) & ~jnp.any(st.bloom_rx)
                     & ~jnp.any(st.f_paused)
                     & ~jnp.any(st.pfc_paused)
                     & jnp.all(st.ing_occ == 0))
    return flows_done & net_empty & signals_clear


def _finish_tail(env, st: SimState, emits, topo_ops, n_ticks: int,
                 step=None, flow_ops=None):
    """Reconstruct ticks [st.t, n_ticks) of a quiescent network in closed
    form, bit-identical to running the flat scan over them.

    Per quiescent tick the full step changes exactly: `t` (+1), the
    sampled histograms (zero bins — folded by `phases.tail_hist`), the
    emit row (constant — `phases.tail_emit_row`), and the per-tick decay /
    congestion-control leaves (`tx_ewma` EWMA decay, DCQCN token refill,
    and the epoch-timer laws — replayed with zero feedback through the
    SAME `phases.cc_laws` the live feedback phase uses, so float op order
    is identical). Everything else is frozen by the `quiescent` predicate.
    A no-op when st.t == n_ticks (no early exit).

    With tracing on the emit row is wider than `tail_emit_row`'s closed
    form, so the constant row comes from evaluating `step` ONCE on the
    quiescent state instead. Every captured channel is a fixed point of
    quiescence — occupancies/pause bits zero, no flow can start (all real
    arrivals precede st.t once their flow completed, phantoms never
    arrive), completions/deliveries frozen, no port eligible to transmit
    (sel -1 / can_tx false) — so the single evaluation yields exactly the
    row the flat scan would emit at every tail tick. The off-spec path
    never calls `step` here, keeping that program byte-identical to the
    untraced build."""
    pc, tm, F = env.cfg.proto, env.cfg.timing, env.F
    zero_i = jnp.zeros((F,), I32)
    zero_f = jnp.zeros((F,), jnp.float32)

    def tick(_, c):
        tx_ewma, tokens, v = c
        # switch_tx: can_tx is all-False -> pure EWMA decay on every port
        tx_ewma = tx_ewma * (1 - 1 / 32)
        # nic_tx: the rate-limited NICs (DCQCN, FairQ) keep refilling
        # their token bucket until the 2.0 cap
        if pc.cc in ("dcqcn", "fairq"):
            tokens = jnp.minimum(tokens + v.rate, 2.0)
        # feedback: drained rings are all zeros
        v = phases.cc_laws(pc, tm, v, zero_i, zero_i, zero_f)
        return tx_ewma, tokens, v

    remaining = jnp.int32(n_ticks) - st.t
    tx_ewma, tokens, v = jax.lax.fori_loop(
        0, remaining, tick,
        (st.tx_ewma, st.tokens, phases.CCVars.of_state(st)))

    st = phases.tail_hist(env, st, topo_ops, n_ticks)
    if env.cfg.trace.enabled:
        _, row = step(st, flow_ops, topo_ops)
    else:
        row = phases.tail_emit_row(env, st)
    tail = jnp.arange(n_ticks, dtype=I32)[:, None] >= st.t
    emits = jnp.where(tail, row[None, :], emits)
    st = st._replace(
        t=jnp.int32(n_ticks), tx_ewma=tx_ewma, tokens=tokens,
        cwnd=v.cwnd, cwnd_ref=v.cwnd_ref, rate=v.rate,
        rate_target=v.rate_target, alpha=v.alpha, ack_seen=v.ack_seen,
        mark_seen=v.mark_seen, cc_timer=v.cc_timer, since_dec=v.since_dec)
    return st, emits


def compiled_runner(dims: TopoDims, cfg: SimConfig, n_flows: int,
                    n_ticks: int, unroll: int = 1, batched: bool = False,
                    segment: int = DEFAULT_SEGMENT, early_exit: bool = True,
                    devices=None):
    """The jitted simulator program for one static signature.

    Keyed on everything that shapes the XLA program: `TopoDims`, the
    protocol/timing config (normalized through `static_cfg` here, so
    ClosParams can never fragment the cache), (padded) flow count, tick
    count, segment width, and the `early_exit` escape hatch. Repeat calls —
    every topology/seed/load of a sweep, or serial runs over same-shaped
    cases — reuse the cached executable instead of recompiling. With
    `batched=True` the returned function takes `FlowOperands` and
    `TopoOperands` with a leading batch axis and vmaps the whole simulation
    over both (still a single compilation for the entire grid; the
    segmented while-loop then runs until every lane is quiescent, masking
    finished lanes). Returns `(state, emits[T, 3 + trace], active_ticks)` —
    `active_ticks` is the tick the run actually simulated to before the
    closed-form tail took over (= n_ticks when no early exit).

    With several `devices` (and `batched=True`) the batch axis is split
    over a one-axis ``lanes`` mesh of them by `shard_map`, so each device
    loops over its own lanes only. Lanes share nothing, so this is
    bit-identical to one device; and XLA cannot partition a Pallas (Mosaic)
    kernel by itself, so it is what lets the kernel path run on several
    chips."""
    return _compiled_runner(dims, static_cfg(cfg), n_flows, n_ticks,
                            unroll, batched, segment, early_exit,
                            tuple(devices) if devices else None)


@functools.lru_cache(maxsize=None)
def _compiled_runner(dims: TopoDims, cfg: SimConfig, n_flows: int,
                     n_ticks: int, unroll: int, batched: bool,
                     segment: int, early_exit: bool, devices):
    init_state, step = make_step(dims, cfg, n_flows,
                                 LANE_AXIS if batched else None)
    env = phases.make_env(dims, cfg, n_flows)
    # emit row width: 3 legacy columns + the opt-in trace channels
    # (0 with the default off-spec, so the buffer shape is unchanged)
    emit_w = EMIT_BASE + trace_layout(cfg.trace, dims.n_ports,
                                      dims.n_switches).width

    def seg_scan(st, flow_ops, topo_ops, length):
        return jax.lax.scan(lambda s, _: step(s, flow_ops, topo_ops),
                            st, None, length=length, unroll=unroll)

    def one_flat(flow_ops, topo_ops):
        st, emits = seg_scan(init_state(), flow_ops, topo_ops, n_ticks)
        return st, emits, st.t

    def one_segmented(flow_ops, topo_ops):
        # a segment never exceeds the horizon (short runs degenerate to
        # one while-loop iteration, or to the remainder scan alone)
        seg = min(segment, n_ticks)
        n_full, rem = divmod(n_ticks, seg)

        def advance(carry, length):
            st, emits = carry
            t0 = st.t
            st, e = seg_scan(st, flow_ops, topo_ops, length)
            with jax.named_scope("runner.emit_write"):
                return st, jax.lax.dynamic_update_slice(
                    emits, e, (t0, jnp.int32(0)))

        def done(st):
            with jax.named_scope("runner.quiescent"):
                return quiescent(st, flow_ops)

        st, emits = jax.lax.while_loop(
            lambda c: (c[0].t < n_full * seg) & ~done(c[0]),
            lambda c: advance(c, seg),
            (init_state(), jnp.zeros((n_ticks, emit_w), I32)))
        if rem:
            # horizon not a segment multiple: run the remainder unless the
            # loop already went quiescent (then the tail covers it)
            st, emits = jax.lax.cond(
                done(st), lambda c: c,
                lambda c: advance(c, rem), (st, emits))
        active = st.t
        with jax.named_scope("runner.tail"):
            st, emits = _finish_tail(env, st, emits, topo_ops, n_ticks,
                                     step=step, flow_ops=flow_ops)
        return st, emits, active

    one = one_flat if not early_exit or n_ticks == 0 else one_segmented

    def go(flow_ops, topo_ops):
        TRACE_EVENTS.append((cfg.proto.name, dims, n_flows, n_ticks,
                             batched))
        if batched:
            return jax.vmap(one, axis_name=LANE_AXIS)(flow_ops, topo_ops)
        return one(flow_ops, topo_ops)

    if devices is not None and len(devices) > 1:
        if not batched:
            raise ValueError("only a batched runner splits over devices")
        lanes = PartitionSpec("lanes")
        # every value is per-lane; the carry starts from constants, which
        # the varying-axis check would otherwise reject
        go = jax.shard_map(go, mesh=Mesh(np.asarray(devices), ("lanes",)),
                           in_specs=lanes, out_specs=lanes, check_vma=False)
    return jax.jit(go)


def run(topo: Topology, flows, cfg: SimConfig, n_ticks: int,
        unroll: int = 1, segment: int = DEFAULT_SEGMENT,
        early_exit: bool = True):
    """Run the simulation for `n_ticks`. Returns (final_state, emits) with
    emits of shape (T, 3 + trace channels) — the 3 legacy columns plus any
    `cfg.trace` capture (see `trace.split_emits` to separate them).

    unroll: ticks inlined per scan iteration. Measured WORSE at 4 on CPU
    (§Perf R9) — the step is gather/scatter-bound, not dispatch-bound — so
    the default stays 1. The active-horizon early exit is on by default
    (bit-identical by construction); `early_exit=False` forces the flat
    scan for A/B timing."""
    n_ticks = int(np.ceil(n_ticks / unroll) * unroll)
    dims = TopoDims.of(topo)
    go = compiled_runner(dims, static_cfg(cfg), flows.n_flows, n_ticks,
                         unroll, segment=segment, early_exit=early_exit)
    st, emits, _ = go(pack_flows(flows, cfg),
                      pack_topo(topo,
                                infinite_buffer=cfg.proto.infinite_buffer))
    return jax.device_get(st), np.asarray(emits)
