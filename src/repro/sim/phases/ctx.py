"""Shared step context: static env, per-tick derived state, scatter helpers.

`PhaseEnv` carries everything that shapes the compiled program (protocol /
timing config + `TopoDims`); `StepCtx` carries the traced values phases hand
to each other within one tick. Fields a phase has not produced yet are None,
so misordered phase composition fails loudly at trace time.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import bloom
from ...kernels.bfc_step import ops as kernel_ops
from ...kernels.bfc_step.ref import pause_threshold
from ..config import SimConfig
from ..topology import TopoDims

I32 = jnp.int32
BIG = np.int32(1 << 20)  # large-but-packable sentinel for priority keys


class PhaseEnv(NamedTuple):
    """Compile-time constants shared by every phase (hashable, static)."""
    cfg: SimConfig           # .clos is unused — topology arrives as operands
    dims: TopoDims
    F: int                   # (padded) flow count
    RING: int                # feedback ring length (worst-case delay + 2)
    RRING: int               # retransmit ring length (rto + 1)
    bparams: bloom.BloomParams
    # name of the vmapped lane axis in a batched runner, None unbatched:
    # a per-tick conditional reduces its predicate over it (`lane_any`)
    lane_axis: Optional[str] = None

    @property
    def P(self) -> int:
        return self.dims.n_ports

    @property
    def NSRV(self) -> int:
        return self.dims.n_servers

    @property
    def NSW(self) -> int:
        return self.dims.n_switches

    @property
    def PROP_MAX(self) -> int:
        # padded wire-ring length; each lane wraps at its own traced
        # `TopoOperands.prop_ticks` <= PROP_MAX
        return self.dims.prop_max

    @property
    def Q(self) -> int:
        return self.cfg.proto.n_queues

    @property
    def CAP(self) -> int:
        return self.cfg.proto.queue_cap

    @property
    def PLCAP(self) -> int:
        return self.cfg.proto.pauselist_cap

    @property
    def H(self) -> int:
        # route width: every lane's routes are padded to it with -1
        return self.dims.hops

    @property
    def S(self) -> int:
        return self.cfg.bloom_stages

    @property
    def TAU(self) -> int:
        return self.cfg.timing.tau_ticks


def make_env(dims: TopoDims, cfg: SimConfig, n_flows: int,
             lane_axis: Optional[str] = None) -> PhaseEnv:
    # feedback ring sized for the worst-case one-way delay of the slowest
    # lane (static so the compiled program is independent of the workload's
    # actual hop counts and of each lane's true prop_ticks: a ring is a
    # pure delay line, so oversizing it never changes when feedback lands)
    return PhaseEnv(cfg=cfg, dims=dims, F=int(n_flows),
                    RING=dims.hops * dims.prop_max + 2,
                    RRING=cfg.timing.rto_ticks + 1,
                    bparams=bloom.BloomParams(cfg.bloom_stages,
                                              cfg.bloom_stage_bits),
                    lane_axis=lane_axis)


def lane_any(env: PhaseEnv, x) -> jnp.ndarray:
    """`jnp.any(x)` over this lane and every other lane of the program.

    The predicate of a per-tick `lax.cond`: under vmap a per-lane predicate
    turns the cond into a select that runs both branches, while one reduced
    over the named lane axis stays a real conditional. Under `shard_map`
    each device reduces over its own lanes only."""
    hit = jnp.any(x)
    if env.lane_axis is None:
        return hit
    return jax.lax.pmax(hit.astype(I32), env.lane_axis) > 0


class StepCtx(NamedTuple):
    """Per-tick values threaded through the phase pipeline.

    Grouped by producing phase; every field is consumed by at least one
    later phase or by the final state assembly in `stats`."""
    # -- phase 0 (derive) ----------------------------------------------------
    t: Optional[jnp.ndarray] = None
    occ: Optional[jnp.ndarray] = None          # (P, Q) pre-tx occupancy
    port_occ: Optional[jnp.ndarray] = None     # (P,)
    sw_occ: Optional[jnp.ndarray] = None       # (NSW,)
    qpaused: Optional[jnp.ndarray] = None      # (P, Q) head-of-queue pause
    th: Optional[jnp.ndarray] = None           # (P,) dynamic pause threshold
    pfc_paused: Optional[jnp.ndarray] = None   # (P,)
    rem_src: Optional[jnp.ndarray] = None      # (F,) incl. this tick's work
    # kernelized switch decision (None on the lax path; see `derive`):
    ksel_q: Optional[jnp.ndarray] = None       # (P,) DRR/SRF pick, -1 = none
    kcan_tx: Optional[jnp.ndarray] = None      # (P,) pick exists
    kocc_after: Optional[jnp.ndarray] = None   # (P, Q) post-tx occupancy
    # -- phase 1 (control) ---------------------------------------------------
    bloom_counts: Optional[jnp.ndarray] = None
    bloom_mid: Optional[jnp.ndarray] = None
    bloom_rx: Optional[jnp.ndarray] = None
    pl: Optional[jnp.ndarray] = None
    pl_head: Optional[jnp.ndarray] = None
    f_paused: Optional[jnp.ndarray] = None
    sfc_ring: Optional[jnp.ndarray] = None     # (RING, F) + this tick's
    #                                            signals (SFC source pause)
    n_sfc: Optional[jnp.ndarray] = None        # () i32 signals sent now
    # -- phase 2 (switch_tx) -------------------------------------------------
    can_tx: Optional[jnp.ndarray] = None       # (P,)
    sel_q: Optional[jnp.ndarray] = None        # (P,) picked queue (garbage
    #                                            where ~can_tx; trace capture
    #                                            masks it with can_tx)
    tx_entry: Optional[jnp.ndarray] = None     # (P,)
    tx_hop: Optional[jnp.ndarray] = None       # (P,)
    qhead: Optional[jnp.ndarray] = None
    qptr: Optional[jnp.ndarray] = None
    qsrf: Optional[jnp.ndarray] = None
    f_cnt: Optional[jnp.ndarray] = None
    f_q: Optional[jnp.ndarray] = None
    d_cnt: Optional[jnp.ndarray] = None
    d_q: Optional[jnp.ndarray] = None
    ing_occ: Optional[jnp.ndarray] = None
    bucket_cnt: Optional[jnp.ndarray] = None
    occ_after: Optional[jnp.ndarray] = None    # (P, Q) post-tx occupancy
    tx_ewma: Optional[jnp.ndarray] = None
    # -- phase 3 (nic_tx) ----------------------------------------------------
    sent: Optional[jnp.ndarray] = None
    tokens: Optional[jnp.ndarray] = None
    nic_ptr: Optional[jnp.ndarray] = None
    nic_tx: Optional[jnp.ndarray] = None       # (NSRV,) bool
    nic_sel: Optional[jnp.ndarray] = None      # (NSRV,)
    # -- phase 4 (arrivals) --------------------------------------------------
    wire_f: Optional[jnp.ndarray] = None
    wire_hop: Optional[jnp.ndarray] = None
    delivered: Optional[jnp.ndarray] = None
    done: Optional[jnp.ndarray] = None
    ack_ring: Optional[jnp.ndarray] = None
    mark_ring: Optional[jnp.ndarray] = None
    u_ring: Optional[jnp.ndarray] = None
    retx_ring: Optional[jnp.ndarray] = None
    qbuf: Optional[jnp.ndarray] = None
    qtail: Optional[jnp.ndarray] = None
    occ_new: Optional[jnp.ndarray] = None      # (P, Q) post-arrival occupancy
    pl_tail: Optional[jnp.ndarray] = None
    dropped: Optional[jnp.ndarray] = None      # (P,) bool
    collide: Optional[jnp.ndarray] = None      # (P,) bool
    needs_alloc: Optional[jnp.ndarray] = None  # (P,) bool
    overflow_ev: Optional[jnp.ndarray] = None  # () i32
    n_pauses: Optional[jnp.ndarray] = None     # () i32
    # -- phase 5 (feedback) --------------------------------------------------
    acked: Optional[jnp.ndarray] = None
    cwnd: Optional[jnp.ndarray] = None
    cwnd_ref: Optional[jnp.ndarray] = None
    rate: Optional[jnp.ndarray] = None
    rate_target: Optional[jnp.ndarray] = None
    alpha: Optional[jnp.ndarray] = None
    ack_seen: Optional[jnp.ndarray] = None
    mark_seen: Optional[jnp.ndarray] = None
    cc_timer: Optional[jnp.ndarray] = None
    since_dec: Optional[jnp.ndarray] = None
    sfc_until: Optional[jnp.ndarray] = None    # (F,) post-landing deadline


def rank_same_key(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = #{j < i : valid[j] and keys[j] == keys[i]} (serialization).

    Sort-based O(P log P): stable-sort by key (invalid lanes pushed to the
    end keep rank relative to nothing), then rank = position - group start.
    Equivalent to the naive O(P^2) pairwise count (see §Perf R9); exactness
    is covered by the simulator integrity tests.

    The arrival hot path no longer calls this five times per tick: the
    three (port, queue)-keyed offsets derive from ONE `ArrivalLayout` sort
    and the two coarse pre-assignment ranks use `pairwise_rank` (no sort).
    Kept as the reference implementation and for one-off callers.
    """
    n = keys.shape[0]
    big = jnp.int32(jnp.iinfo(np.int32).max)
    k = jnp.where(valid, keys, big)
    order = jnp.argsort(k, stable=True)
    ks = k[order]
    pos = jnp.arange(n, dtype=I32)
    new_group = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_group, pos, 0))
    rank_sorted = pos - group_start
    rank = jnp.zeros((n,), I32).at[order].set(rank_sorted)
    # invalid lanes must rank as if absent; they never contribute, and their
    # own rank is unused by callers, but keep parity with the naive version
    return jnp.where(valid, rank, jnp.zeros((), I32)).astype(I32)


def pairwise_rank(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """`rank_same_key` semantics via the closed O(N^2) pairwise count.

    No sort: an (N, N) equality/triangle mask reduction, cheaper than an
    argsort for the lane counts this simulator runs (N = ports, a few
    hundred). Used for the two coarse arrival ranks (per-switch admission,
    per-port allocation) that must be computed BEFORE the queue assignment
    exists and therefore cannot ride the `ArrivalLayout` permutation."""
    n = keys.shape[0]
    idx = jnp.arange(n)
    rank = ((keys[None, :] == keys[:, None])
            & (idx[None, :] < idx[:, None])
            & valid[None, :]).sum(axis=1).astype(I32)
    return jnp.where(valid, rank, jnp.zeros((), I32))


class ArrivalLayout(NamedTuple):
    """ONE stable argsort over a composite serialization key; every
    same-tick rank/offset of the arrival phase derives from this single
    permutation as a segment position (see `subset_rank`).

    `key` carries INT32_MAX where `valid` is False, so invalid lanes sort
    to the end as their own group; `group_start[s]` is, in sorted order,
    the position of the first lane with the same key as position `s`."""
    key: jnp.ndarray          # (N,) composite key, INT32_MAX where ~valid
    order: jnp.ndarray        # (N,) THE permutation (stable argsort of key)
    unsort: jnp.ndarray       # (N,) inverse permutation
    group_start: jnp.ndarray  # (N,) sorted-order index of each group head
    valid: jnp.ndarray        # (N,) bool


def build_layout(keys: jnp.ndarray, valid: jnp.ndarray) -> ArrivalLayout:
    """Sort once; rank many. The only per-tick sort of the arrival phase.

    Stability matters twice over: lanes of one key group stay in original
    index order (so a `subset_rank` at the *same* key granularity is
    bit-identical to `rank_same_key` over that subset), and repeat calls
    with equal operands produce the identical permutation."""
    n = keys.shape[0]
    big = jnp.int32(jnp.iinfo(np.int32).max)
    k = jnp.where(valid, keys, big)
    order = jnp.argsort(k, stable=True)
    ks = k[order]
    pos = jnp.arange(n, dtype=I32)
    new_group = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_group, pos, 0))
    unsort = jnp.zeros((n,), I32).at[order].set(pos)
    return ArrivalLayout(key=k, order=order, unsort=unsort,
                         group_start=group_start, valid=valid)


def subset_rank(layout: ArrivalLayout, mask: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = #{j < i : mask[j] and key[j] == key[i]} for mask[i] lanes.

    Requires `mask & ~layout.valid` empty (subsets of the layout's valid
    set — the arrival phase's masks are nested: over ⊆ accept ⊆ arrivals).
    A segmented exclusive prefix count over the already-sorted order: the
    layout's groups are the key's equivalence classes and stable sorting
    preserved index order inside them, so the count of `mask` lanes earlier
    in the group equals the count earlier in original index order — i.e.
    bit-identical to `rank_same_key(where(mask, key, -2), mask)` without
    re-sorting."""
    ms = mask[layout.order].astype(I32)
    excl = jnp.cumsum(ms) - ms                       # subset lanes before s
    rank_sorted = excl - excl[layout.group_start]    # ... within s's group
    return jnp.where(mask, rank_sorted[layout.unsort],
                     jnp.zeros((), I32)).astype(I32)


def counts_per_key(keys, valid, num):
    return jax.ops.segment_sum(valid.astype(I32), jnp.where(valid, keys, 0),
                               num_segments=num)


def hop_of_port(routes, f, p):
    """Which hop of flow f's route is port p (f, p broadcastable)."""
    return jnp.argmax(routes[f] == p[..., None], axis=-1).astype(I32)


def derive(env: PhaseEnv, st, ops, topo) -> StepCtx:
    """Phase 0: per-tick derived state.

    Queue occupancy, per-switch buffer fill, the head-of-queue pause bits
    from the received Bloom snapshot (re-evaluated every tick == "recompute
    after every dequeue"), the dynamic per-queue pause threshold, PFC
    hysteresis, and this tick's flow arrivals at the sources."""
    pc, tm = env.cfg.proto, env.cfg.timing
    P, Q, CAP = env.P, env.Q, env.CAP

    t = st.t
    occ = st.qtail - st.qhead                          # (P, Q)
    port_occ = occ.sum(axis=1)                         # (P,)
    sw_occ = jax.ops.segment_sum(
        jnp.where(topo.port_is_nic, 0, port_occ),
        jnp.maximum(topo.port_switch, 0), num_segments=env.NSW)  # (NSW,)

    head_entry = jnp.take_along_axis(
        st.qbuf, (st.qhead % CAP)[..., None], axis=2)[..., 0]   # (P, Q)
    head_f = jnp.maximum(head_entry >> 1, 0)
    if pc.backpressure:
        head_pos = ops.fpos[head_f]                             # (P, Q, S)
        with jax.named_scope("derive.head_pause"):
            qpaused = bloom.check(st.bloom_rx[:, None], head_pos) & (occ > 0)
    else:
        qpaused = jnp.zeros((P, Q), bool)

    with jax.named_scope("switch_decision"):
        n_active = jnp.maximum(((occ > 0) & ~qpaused).sum(axis=1), 1)
        th = pause_threshold(n_active, tm.pause_window)            # (P,)

    # PFC state (hysteresis: pause above th, resume below th/2)
    if pc.pfc:
        free_buf = jnp.maximum(topo.buffer_limit - sw_occ, 0)
        pfc_th = jnp.maximum((pc.pfc_frac * free_buf).astype(I32), 2)
        th_here = jnp.where(topo.feeds >= 0,
                            pfc_th[jnp.maximum(topo.feeds, 0)],
                            jnp.int32(1 << 30))
        pfc_paused = jnp.where(st.pfc_paused,
                               st.ing_occ > th_here // 2,
                               st.ing_occ > th_here)
    else:
        pfc_paused = jnp.zeros((P,), bool)

    # flow arrivals at sources
    newly = ops.arrival == t
    rem_src = st.rem_src + jnp.where(newly, ops.size, 0)

    # kernelized switch step (ProtoConfig.kernel_impl != 'lax'): ONE fused
    # Pallas call computes the pause threshold, the DRR/SRF pick, and the
    # post-tx occupancy for every port; `switch_tx` consumes the stashed
    # decision instead of recomputing it in lax. The decision inputs (occ,
    # qpaused, qptr/qsrf, pfc_paused, port_is_nic) are all fixed by the
    # time `derive` ends — `control` mutates none of them — so computing
    # the pick here is equivalent to computing it in switch_tx.
    # `engine.static_cfg` resolved kernel_impl to a concrete
    # 'pallas'/'interpret' before this program was traced.
    ksel = kcan = kocc = None
    if pc.kernel_impl != "lax":
        blocked = pfc_paused | topo.port_is_nic
        srf_key = (jnp.minimum(st.qsrf, BIG) if pc.scheduler == "srf"
                   else None)
        with jax.named_scope("switch_decision"):
            _, th, _, ksel, kcan, kocc = kernel_ops.fused(
                occ, qpaused, st.qptr, blocked, srf_key=srf_key,
                pause_window=tm.pause_window, scheduler=pc.scheduler,
                impl=pc.kernel_impl)

    return StepCtx(t=t, occ=occ, port_occ=port_occ, sw_occ=sw_occ,
                   qpaused=qpaused, th=th, pfc_paused=pfc_paused,
                   rem_src=rem_src, ksel_q=ksel, kcan_tx=kcan,
                   kocc_after=kocc)
