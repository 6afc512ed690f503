"""Batched experiment sweeps: one compiled simulator, a whole parameter grid.

The paper's headline results are sweeps over protocol x topology x workload
x load x incast x seed. Compiling the ~800-line scan once per grid point
dominated wall-clock; this module amortizes one XLA build across every grid
point that shares a program signature (cf. the ns-3 sweep harnesses shipped
with HPCC and BFC, which amortize one binary build over the whole grid).

Padding contracts
-----------------
Workloads in a batch are padded to a common flow count ``F_max`` (rounded up
to ``pad_multiple`` so differently-sized grids still hit the same compiled
program). Padded "phantom" flows are inert by construction:

* ``arrival_tick = engine.PHANTOM_ARRIVAL`` (2**30) — beyond any simulated
  horizon, so a phantom never starts, is never eligible at the NIC, and
  never transmits a packet;
* ``size_pkts = 0`` — even if started it would have nothing to send;
* ``routes = -1`` everywhere — a phantom is never looked up by any hop.

Routes are also padded to the batch's route width ``TopoDims.hops`` with
-1 columns: a leaf-spine lane (4 hops) in a batch with a fat-tree (6) never
takes its extra hops, since a packet leaves the fabric where its route
ends.

Topologies in a batch are likewise padded to a common ``TopoDims`` (max
ports / servers / switches / ``hops`` / ``prop_max``, the padded wire-ring
length — each lane's wires wrap at its own traced
``TopoOperands.prop_ticks`` modulus, so link latency rides the batch axis
too). Phantom
ports/switches/servers are inert by the mirror argument:
no route names a phantom port, so it never holds occupancy and never
transmits; phantom servers never source flows, so their NIC lane never wins
the DRR segment-min; ``port_valid`` / ``switch_valid`` masks keep them out
of the sampled histograms. Both padded runs are bit-identical to their
unpadded serial counterparts (tests/test_sim_padding.py,
tests/test_sim_topo_sweep.py), and a vmapped batch is bit-identical to the
corresponding serial runs (tests/test_sim_sweep.py).

Compile-cache contract
----------------------
``engine.compiled_runner`` is keyed on (TopoDims, static_cfg(SimConfig), F,
n_ticks, unroll, batched) — ClosParams is NOT part of the key; the fabric
arrives as traced ``TopoOperands``. One batched program is compiled per
*protocol variant* (protocol flags are Python-level branches in the phase
pipeline, so e.g. BFC and DCTCP can never share a program); all topologies/
seeds/loads/workloads of that variant ride the batch axis of a single
compilation. `run_grid` therefore groups its cases by ``static_cfg`` and
falls back to per-group (still batched) execution when a grid mixes
protocol variants. `engine.trace_count()` counts actual XLA traces, which
tests and scripts/trace_guard.py use to assert the one-compilation
property.

Execution
---------
*Where* a grid runs — chunk width, device placement, host/device overlap —
is owned by `repro.sim.exec`. ``run_batch`` derives (or accepts) an
``exec.ExecPlan``: the planner measures the per-lane SimState footprint via
``lane_state_bytes`` (dominated by the F x H rings and the P x Q x CAP
queue buffers), reads live device stats to auto-derive the chunk width
(``max_batch_bytes`` remains as an explicit override), and the dispatcher
shards each chunk's lanes across every local device while double-buffering
host readback — all chunks still reuse the ONE compiled program (the tail
chunk is padded with repeats of lane 0, padded results dropped).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import engine, metrics
from .config import SimConfig
from .engine import FlowOperands, SimState
from .exec.dispatch import span
from .topology import (TopoDims, TopoOperands, Topology, build_cached,
                       pack_topo)
from .workload import FlowSet

# Default padding quantum for F_max: coarse enough that ragged grids share
# compiled programs, fine enough not to waste memory on tiny sims.
PAD_MULTIPLE = 64

# SimState leaves carrying a per-flow axis (axis 0 after the batch axis is
# selected away), used to trim padded state back to a workload's true F.
_PER_FLOW_AXIS0 = {
    "rem_src", "sent", "acked", "delivered", "done", "cwnd", "cwnd_ref",
    "rate", "rate_target", "tokens", "alpha", "ack_seen", "mark_seen",
    "cc_timer", "since_dec", "f_q", "f_cnt", "f_paused", "sfc_until",
}
_PER_FLOW_AXIS1 = {"ack_ring", "mark_ring", "u_ring", "retx_ring",
                   "sfc_ring"}
# ... and the leaves carrying topology axes, trimmed back to a fabric's
# true port/server/switch counts after a padded multi-topology run.
_PER_PORT_AXIS0 = {
    "qbuf", "qhead", "qtail", "qptr", "qsrf", "d_q", "d_cnt",
    "bloom_counts", "bloom_mid", "bloom_rx", "pl", "pl_head", "pl_tail",
    "ing_occ", "pfc_paused", "wire_f", "wire_hop", "tx_ewma",
}
_PER_SERVER_AXIS0 = {"nic_ptr"}
_PER_SERVER_AXIS1 = {"d_q", "d_cnt"}
_PER_SWITCH_AXIS0 = {"bucket_cnt"}
# ... and the per-(flow, hop) tables, trimmed back to the fabric's own
# route width `TopoDims.hops` ...
_PER_HOP_AXIS1 = {"f_q", "f_cnt", "f_paused"}
# ... and the leaves whose shapes scale with the padded wire-ring length
# `TopoDims.prop_max`: the wires themselves (axis 1 = PROP_MAX) and the
# feedback delay lines (axis 0 = hops * prop_max + 2).
_PER_PROP_AXIS1 = {"wire_f", "wire_hop"}
_FB_RING_AXIS0 = {"ack_ring", "mark_ring", "u_ring", "sfc_ring"}


def pad_flowset(flows: FlowSet, f_max: int,
                hops: Optional[int] = None) -> FlowSet:
    """Append inert phantom flows until the set holds `f_max` flows, and
    -1 route columns until routes are `hops` wide (default: as they are)."""
    pad = f_max - flows.n_flows
    routes = np.asarray(flows.routes, np.int32)
    wide = (hops or routes.shape[1]) - routes.shape[1]
    if pad < 0 or wide < 0:
        raise ValueError(f"f_max={f_max}, hops={hops} < the set's "
                         f"{flows.n_flows} flows x {routes.shape[1]} hops")
    if wide:
        routes = np.pad(routes, ((0, 0), (0, wide)), constant_values=-1)
        flows = replace(flows, routes=routes)
    if pad == 0:
        return flows
    return FlowSet(
        src=np.concatenate([np.asarray(flows.src, np.int32),
                            np.zeros(pad, np.int32)]),
        dst=np.concatenate([np.asarray(flows.dst, np.int32),
                            np.zeros(pad, np.int32)]),
        size_pkts=np.concatenate([np.asarray(flows.size_pkts, np.int32),
                                  np.zeros(pad, np.int32)]),
        arrival_tick=np.concatenate(
            [np.asarray(flows.arrival_tick, np.int32),
             np.full(pad, engine.PHANTOM_ARRIVAL, np.int32)]),
        routes=np.concatenate([routes, np.full((pad, routes.shape[1]), -1,
                                               np.int32)]),
        ideal_fct=np.concatenate([np.asarray(flows.ideal_fct, np.int32),
                                  np.ones(pad, np.int32)]),
        fid=np.concatenate([np.asarray(flows.fid, np.int32),
                            np.zeros(pad, np.int32)]),
        is_incast=np.concatenate([np.asarray(flows.is_incast, bool),
                                  np.zeros(pad, bool)]),
        horizon=flows.horizon)


def padded_count(flowsets: Sequence[FlowSet],
                 pad_multiple: int = PAD_MULTIPLE) -> int:
    f_max = max(f.n_flows for f in flowsets)
    return int(-(-max(f_max, 1) // pad_multiple) * pad_multiple)


def stack_operands(flowsets: Sequence[FlowSet], cfg: SimConfig,
                   f_max: int, hops: Optional[int] = None) -> FlowOperands:
    """Pad every FlowSet to `f_max` flows and `hops` route columns (default:
    the widest set's) and stack operands on a batch axis."""
    hops = hops or max(np.shape(f.routes)[1] for f in flowsets)
    packed = [engine.pack_flows(pad_flowset(f, f_max, hops), cfg)
              for f in flowsets]
    return FlowOperands(*[jnp.stack(leaves) for leaves in zip(*packed)])


def _topo_list(topo: Union[Topology, Sequence[Topology]],
               k: int) -> List[Topology]:
    if isinstance(topo, Topology):
        return [topo] * k
    topos = list(topo)
    if len(topos) != k:
        raise ValueError(f"{len(topos)} topologies for {k} workloads")
    return topos


def batch_dims(topos: Sequence[Topology]) -> TopoDims:
    """The common padded `TopoDims` of a (possibly mixed) topology batch."""
    dims = TopoDims.of(topos[0])
    for t in topos[1:]:
        dims = dims.union(TopoDims.of(t))
    return dims


def stack_topos(topos: Sequence[Topology], cfg: SimConfig,
                dims: TopoDims) -> TopoOperands:
    """Pad every fabric to `dims` and stack operands on a batch axis."""
    packed = [pack_topo(t, infinite_buffer=cfg.proto.infinite_buffer,
                        dims=dims) for t in topos]
    return TopoOperands(*[jnp.stack(leaves) for leaves in zip(*packed)])


def lane_state_bytes(dims: TopoDims, cfg: SimConfig, n_flows: int,
                     n_ticks: int = 0) -> int:
    """Bytes one batch lane holds on device: the padded SimState (~F x H +
    P x Q x CAP ints, measured exactly via eval_shape — no allocation) plus
    its (T, 3 + trace channels) emit rows. Used to chunk grids against
    `max_batch_bytes`.

    Because the measurement walks the shapes `make_step(dims, ...)` would
    allocate, it automatically includes the `dims.prop_max`-padded wire
    rings (P x prop_max x 2), the (F, dims.hops) hop tables and the
    feedback delay lines ((hops * prop_max + 2) x F x 4): a batch padded to
    a long wire or a wide route bills every lane at the padded size, and
    the exec planner's chunk width shrinks accordingly."""
    init_state, _ = engine.make_step(dims, engine.static_cfg(cfg), n_flows)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(init_state))
    state = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    emit_w = engine.EMIT_BASE + engine.trace_layout(
        cfg.trace, dims.n_ports, dims.n_switches).width
    return state + n_ticks * emit_w * 4


def trim_state(state: SimState, n_flows: int,
               dims: Optional[TopoDims] = None) -> SimState:
    """Trim the per-flow — and, given `dims`, per-port/server/switch/hop/
    prop — leaves of an (unbatched) SimState back to the workload's true F
    and the fabric's true shapes, dropping the phantom tails a padded run
    carries.

    Hop tables are trimmed to `dims.hops` columns and wire rings to
    `dims.prop_max` slots (hops a route never takes and slots beyond a
    lane's true delay are never-touched padding). The feedback delay lines
    are *re-indexed* rather than sliced: two runs padded to different
    `hops * prop_max` store the same pending feedback at different
    absolute rows (the ring length is the wrap modulus), so rows are
    rotated to offset-from-`state.t` order and cut at the fabric's own
    worst-case delay, `dims.hops * dims.prop_max + 2` rows — after which a
    padded run is leaf-for-leaf comparable with its unpadded serial
    twin."""
    t = int(np.asarray(state.t))
    out = {}
    for name, leaf in state._asdict().items():
        v = np.asarray(leaf)
        if name in _PER_FLOW_AXIS0:
            v = v[:n_flows]
        elif name in _PER_FLOW_AXIS1:
            v = v[:, :n_flows]
        if dims is not None:
            if name in _PER_PORT_AXIS0:
                v = v[:dims.n_ports]
            elif name in _PER_SERVER_AXIS0:
                v = v[:dims.n_servers]
            elif name in _PER_SWITCH_AXIS0:
                v = v[:dims.n_switches]
            if name in _PER_SERVER_AXIS1:
                v = v[:, :dims.n_servers]
            if name in _PER_HOP_AXIS1:
                v = v[:, :dims.hops]
            if name in _PER_PROP_AXIS1:
                v = v[:, :dims.prop_max]
            elif name in _FB_RING_AXIS0:
                ring = dims.hops * dims.prop_max + 2
                if ring > v.shape[0]:
                    raise ValueError(
                        f"trim_state: dims.hops={dims.hops} and "
                        f"prop_max={dims.prop_max} imply a {ring}-row "
                        "feedback ring but the "
                        f"state holds {v.shape[0]} rows — pass the "
                        "fabric's own TopoDims, not a batch union")
                v = v[(t + np.arange(ring)) % v.shape[0]]
        out[name] = v
    return SimState(**out)


def select_config(batched_state: SimState, k: int,
                  n_flows: Optional[int] = None,
                  dims: Optional[TopoDims] = None) -> SimState:
    """Extract config `k` from a batched SimState, trimming per-flow (and,
    given `dims`, per-port/server/switch) leaves back to the case's true
    shapes so it is leaf-for-leaf comparable with an unpadded serial
    `engine.run`."""
    lane = SimState(**{name: np.asarray(leaf)[k]
                       for name, leaf in batched_state._asdict().items()})
    if n_flows is None and dims is None:
        return lane
    return trim_state(lane, n_flows if n_flows is not None
                      else lane.done.shape[0], dims)


def run_batch(topo: Union[Topology, Sequence[Topology]],
              flowsets: Sequence[FlowSet], cfg: SimConfig, n_ticks: int,
              unroll: int = 1, pad_multiple: int = PAD_MULTIPLE,
              max_batch_bytes: Optional[int] = None,
              devices: Optional[Sequence] = None, auto_budget: bool = True,
              plan: Optional["object"] = None, store=None,
              early_exit: bool = True, resume: bool = False):
    """Run K workloads under one protocol config as a single vmapped,
    jitted program. `topo` is one Topology shared by every lane or a
    per-lane sequence (mixed fabrics are padded to a common `TopoDims`, so
    topology rides the batch axis of the SAME compilation). Returns
    (batched_state, emits[K, T, 3]); use `select_config` to view one lane.

    Execution routes through an `exec.ExecPlan` (pass one via `plan` to
    override placement entirely): the planner caps the device-resident
    SimState footprint at `max_batch_bytes` when given, else auto-derives a
    budget from live device/host memory stats (`auto_budget=False` forgoes
    the cap). Oversized grids run as equal-width chunks of one shared
    executable, each chunk sharded across `devices` (default: all local
    devices) and double-buffered against host readback; a `store`
    (`exec.RunStore`) spools chunks to disk as they land, and
    `resume=True` (requires a store) reuses the chunks an interrupted run
    of this protocol already journaled, recomputing only the rest (see
    `exec.resume`). `early_exit`
    False forces the flat (non-segmented) runner for A/B timing — per-lane
    active tick counts land in `exec.last_active_ticks()`."""
    from . import exec as exec_
    K = len(flowsets)
    topos = _topo_list(topo, K)
    dims = batch_dims(topos)
    f_max = padded_count(flowsets, pad_multiple)
    n_ticks = int(np.ceil(n_ticks / unroll) * unroll)

    if plan is None:
        budget = (max_batch_bytes if max_batch_bytes is not None
                  else ("auto" if auto_budget else None))
        # the shapes that key the program, and the state one lane holds
        with span("repro.exec.plan", lanes=K, ports=dims.n_ports,
                  servers=dims.n_servers, switches=dims.n_switches,
                  hops=dims.hops, flows=f_max) as sp:
            plan = exec_.plan(dims, cfg, f_max, n_ticks, K,
                              devices=devices, budget=budget, unroll=unroll,
                              early_exit=early_exit)
            sp.counts["lane_bytes"] = plan.per_lane_bytes
    return exec_.execute(plan, topos, flowsets, cfg, store=store,
                         tag=cfg.proto.name, resume=resume)


@dataclass
class CaseResult:
    """One grid point of a sweep, unpacked back to host."""
    label: str
    proto: str
    cfg: SimConfig
    flows: FlowSet
    state: SimState            # per-flow/topo leaves trimmed to true shapes
    emits: np.ndarray          # (T, 3)
    metrics: Optional[metrics.RunMetrics] = None


def _case_topo(cfg: SimConfig, default: Topology) -> Topology:
    """The fabric a case runs on: its own `cfg.clos` (a leaf-spine's
    `ClosParams` or a fat-tree's `FatTreeParams`; the topology is part of
    the per-case configuration), materialized through the build cache;
    `default` is reused when it already matches."""
    if cfg.clos == default.params:
        return default
    return build_cached(cfg.clos)


def run_grid(topo: Topology,
             cases: Sequence[Tuple[str, SimConfig, FlowSet]],
             n_ticks: Optional[int] = None, drain: int = 20_000,
             unroll: int = 1, pad_multiple: int = PAD_MULTIPLE,
             summarize: bool = True,
             max_batch_bytes: Optional[int] = None,
             devices: Optional[Sequence] = None, auto_budget: bool = True,
             store=None, early_exit: bool = True,
             resume: bool = False) -> List[CaseResult]:
    """Run an arbitrary (label, SimConfig, FlowSet) grid.

    Each case runs on the fabric named by its own ``cfg.clos`` (``topo`` is
    the default/fallback instance for cases that match it). Cases are
    grouped by ``engine.static_cfg``: each group — including MIXED
    topologies, which are padded to a common `TopoDims` — runs as ONE
    vmapped compilation (the serial fallback across protocol variants —
    their Python-level branches produce different programs by
    construction). All groups share `n_ticks` (default: max horizon +
    drain) so same-shaped protocol groups can still share executables
    across calls. `devices` / `auto_budget` / `max_batch_bytes` / `store`
    / `resume` configure each group's `exec.ExecPlan` (see `run_batch`;
    with `resume=True` each protocol group independently reuses whatever
    chunks its interrupted run spooled).

    The call is one `repro.sweep.run_grid` span (`exec.dispatch.span`);
    each protocol group, and each case's selection and summary, are
    spans under it."""
    with span("repro.sweep.run_grid", lanes=len(cases)):
        if n_ticks is None:
            n_ticks = int(max(f.horizon for _, _, f in cases) + drain)
        # group key: the compile signature — the protocol/timing config
        # alone. NOTHING about a fabric keys the grouping: ports/servers/
        # switches pad to a union TopoDims and link latency wraps at the
        # traced per-lane prop_ticks modulus, so mixed-latency grids batch
        # into one program.
        groups: Dict[SimConfig, List[int]] = {}
        for i, (_, cfg, _) in enumerate(cases):
            groups.setdefault(engine.static_cfg(cfg), []).append(i)

        topos = [_case_topo(cfg, topo) for _, cfg, _ in cases]
        results: List[Optional[CaseResult]] = [None] * len(cases)
        for idxs in groups.values():
            with span("repro.sweep.group", lanes=len(idxs)):
                flowsets = [cases[i][2] for i in idxs]
                group_topos = [topos[i] for i in idxs]
                cfg = cases[idxs[0]][1]
                st, emits = run_batch(
                    group_topos, flowsets, cfg, n_ticks, unroll,
                    pad_multiple, max_batch_bytes=max_batch_bytes,
                    devices=devices, auto_budget=auto_budget, store=store,
                    early_exit=early_exit, resume=resume)
                for k, i in enumerate(idxs):
                    label, case_cfg, flows = cases[i]
                    case_topo = group_topos[k]
                    with span("repro.sweep.select", case=i):
                        state_k = select_config(st, k, flows.n_flows,
                                                TopoDims.of(case_topo))
                    m = None
                    if summarize:
                        with span("repro.sweep.summarize", case=i):
                            m = metrics.summarize(
                                label, state_k, emits[k], flows,
                                n_links=case_topo.n_ports, occ_bin_ref=(
                                    case_topo.params.switch_buffer_pkts),
                                cap=case_cfg.proto.queue_cap)
                    results[i] = CaseResult(
                        label=label, proto=case_cfg.proto.name,
                        cfg=case_cfg, flows=flows, state=state_k,
                        emits=emits[k], metrics=m)
        return results
