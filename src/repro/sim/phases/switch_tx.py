"""Phase 2: switch egress transmissions (paper §3.2).

Every unpaused, non-empty switch egress port dequeues at most one packet
per tick: DRR (rotating pointer) or SRF (smallest-remaining-first key) picks
the queue, the head packet leaves its ring buffer, and all per-flow /
per-dest / hash-table / PFC bookkeeping records the departure. Flows whose
last queued packet departs release their queue and (if paused) their
upstream Bloom-filter bits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import bloom
from .ctx import BIG, I32, PhaseEnv, StepCtx, hop_of_port


def switch_tx(env: PhaseEnv, st, ops, topo, ctx: StepCtx) -> StepCtx:
    pc = env.cfg.proto
    P, Q, F, CAP = env.P, env.Q, env.F, env.CAP
    NSRV, NSW = env.NSRV, env.NSW
    p_ar = jnp.arange(P)
    q_ar = jnp.arange(Q)

    occ, f_paused = ctx.occ, ctx.f_paused
    if ctx.kcan_tx is not None:
        # kernelized decision path (ProtoConfig.kernel_impl): `derive` ran
        # the fused Pallas step; reuse its pick. The kernel reports "no
        # eligible queue" as sel -1 where this path's packed argmin
        # degenerates to queue 0 — normalize so every downstream
        # gather/scatter is bit-identical to the lax pick.
        can_tx = ctx.kcan_tx
        sel_q = jnp.where(can_tx, ctx.ksel_q, 0)
    else:
        with jax.named_scope("switch_decision"):
            eligible = (occ > 0) & ~ctx.qpaused & ~ctx.pfc_paused[:, None] \
                & ~topo.port_is_nic[:, None]
            if pc.scheduler == "srf":
                key = jnp.minimum(st.qsrf, BIG)
            else:
                key = (q_ar[None, :] - st.qptr[:, None]) % Q
            key = jnp.where(eligible, key, BIG + 1)
            packed = key * Q + q_ar[None, :]               # fits int32
            sel_q = (jnp.min(packed, axis=1) % Q).astype(I32)
            can_tx = eligible[p_ar, sel_q]
    tx_entry = jnp.where(
        can_tx, st.qbuf[p_ar, sel_q, st.qhead[p_ar, sel_q] % CAP], -1)
    tx_f = jnp.maximum(tx_entry >> 1, 0)
    tx_hop = hop_of_port(ops.routes, tx_f, p_ar)
    qhead = st.qhead.at[p_ar, sel_q].add(can_tx.astype(I32))
    if pc.scheduler == "drr":
        qptr = jnp.where(can_tx, sel_q + 1, st.qptr)
    else:
        qptr = st.qptr

    # flow count decrement at this hop; detect departures (count -> 0)
    f_cnt = st.f_cnt.at[tx_f, tx_hop].add(-can_tx.astype(I32))
    departed = can_tx & (f_cnt[tx_f, tx_hop] == 0)
    dep_f = jnp.where(departed, tx_f, F)               # OOB-drop index
    was_paused = f_paused[tx_f, tx_hop] & departed
    up_of_tx = ops.routes[tx_f, jnp.maximum(tx_hop - 1, 0)]
    bloom_counts = ctx.bloom_counts
    if pc.backpressure:
        bloom_counts = bloom.add_batch(
            bloom_counts, jnp.maximum(up_of_tx, 0), ops.fpos[tx_f],
            jnp.where(was_paused, -1, 0))
        f_paused = f_paused.at[dep_f, tx_hop].set(False)
    f_q = st.f_q.at[dep_f, tx_hop].set(-1)
    # dest-keyed bookkeeping
    d_cnt, d_q = st.d_cnt, st.d_q
    if pc.queue_key == "dest":
        d_cnt = d_cnt.at[p_ar, ops.dst[tx_f]].add(-can_tx.astype(I32))
        d_gone = can_tx & (d_cnt[p_ar, ops.dst[tx_f]] == 0)
        d_q = d_q.at[p_ar, jnp.where(d_gone, ops.dst[tx_f], NSRV)].set(-1)
    # PFC ingress accounting (packet left the downstream buffer)
    ing_occ = st.ing_occ.at[jnp.maximum(up_of_tx, 0)].add(
        -(can_tx & (tx_hop > 0)).astype(I32))
    # hash-table departure
    bucket_cnt = st.bucket_cnt.at[
        jnp.maximum(topo.port_switch, 0), ops.fbucket[tx_f]].add(
        -departed.astype(I32))
    # reset SRF key when queue empties (occupancy update comes from the
    # fused kernel when it ran — identical math, already materialized)
    occ_after = (ctx.kocc_after if ctx.kocc_after is not None
                 else occ.at[p_ar, sel_q].add(-can_tx.astype(I32)))
    qsrf = jnp.where(
        (occ_after == 0) & (q_ar[None, :] == sel_q[:, None])
        & can_tx[:, None],
        BIG, st.qsrf)
    tx_ewma = st.tx_ewma * (1 - 1 / 32) + can_tx.astype(jnp.float32) / 32

    return ctx._replace(can_tx=can_tx, sel_q=sel_q, tx_entry=tx_entry,
                        tx_hop=tx_hop,
                        qhead=qhead, qptr=qptr, qsrf=qsrf, f_cnt=f_cnt,
                        f_q=f_q, f_paused=f_paused, d_cnt=d_cnt, d_q=d_q,
                        ing_occ=ing_occ, bucket_cnt=bucket_cnt,
                        occ_after=occ_after, tx_ewma=tx_ewma,
                        bloom_counts=bloom_counts)
