"""Phase 1: tau-boundary control work (paper §3.3) + SFC signalling.

Pops at most one to-be-resumed flow per (port, queue) per tau from the
resume ring (the paper's buffer optimization; disabled by the
`resume_limit=False` ablation), clears its pause bit, decrements the
upstream counting Bloom filter, and rotates the filter pipeline
counts -> in-flight snapshot -> applied snapshot every tau (modeling pause
frame propagation delay). The pop runs only on a tick where some lane of
the program has something to pop (docs/ARCHITECTURE.md, "The phase
pipeline").

The resume gate compares occupancy against `ctx.th` — on the kernelized
switch path (`ProtoConfig.kernel_impl`) that threshold comes from the
fused Pallas step `derive` ran, bit-identical to the inline lax ceil.

With `proto.source_signal` (SFC, arXiv 2305.00538) this phase also runs
the switches' control plane for source flow control: every tau, each
switch scans its egress queues and, for every flow with packets queued at
an egress port whose occupancy exceeds `sfc_threshold`, launches a pause
signal straight back to that flow's sending NIC. The signal carries the
port's drain time (occupancy in ticks, capped at `sfc_max_pause`) and
rides the `sfc_ring` delay line for `hop * prop_ticks + 1` ticks — the
wire distance from the congested switch back to the source, which for a
first-hop ToR is a couple of ticks instead of an end-to-end RTT. The
`feedback` phase lands signals (max-combining concurrent ones) into
`sfc_until`; `nic_tx` gates eligibility on it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import bloom
from .ctx import I32, PhaseEnv, StepCtx, hop_of_port, lane_any

# popping queues one resume pass handles; a tau with more pops takes more
# passes, so the pop's cost follows the pops and not the fabric's queues
RESUME_CHUNK = 128


def control(env: PhaseEnv, st, ops, topo, ctx: StepCtx) -> StepCtx:
    pc = env.cfg.proto
    F = env.F

    is_tau = (ctx.t % env.TAU) == 0
    bloom_counts, bloom_mid, bloom_rx = (st.bloom_counts, st.bloom_mid,
                                         st.bloom_rx)
    pl_head, pl = st.pl_head, st.pl
    f_paused = st.f_paused
    if pc.backpressure:
        pending = st.pl_tail > pl_head
        below = ctx.occ < ctx.th[:, None]
        if pc.resume_limit:
            do_pop = pending & below & is_tau   # <=1 per queue per tau
        else:
            do_pop = pending & below            # ablation: no throttling

        def resume(carry):
            with jax.named_scope("control.resume"):
                return _pop_resumes(env, st, ops, do_pop, *carry)

        # With nothing to pop `resume` is the identity, so a tick on which
        # no lane pops skips it without changing a bit (with `resume_limit`,
        # 11 ticks of 12). The predicate is reduced over the program's lanes
        # (`lane_any`): a per-lane one would make vmap run both branches.
        pl_head, f_paused, bloom_counts = jax.lax.cond(
            lane_any(env, do_pop), resume, lambda carry: carry,
            (pl_head, f_paused, bloom_counts))
        # rotate the filter pipeline every tau (models propagation delay)
        bloom_rx = jnp.where(is_tau, bloom_mid, bloom_rx)
        bloom_mid = jnp.where(is_tau, bloom.snapshot(bloom_counts),
                              bloom_mid)

    # SFC: near-source pause signalling (see module docstring)
    sfc_ring, n_sfc = st.sfc_ring, jnp.int32(0)
    if pc.source_signal:
        H = env.H
        f_ar = jnp.arange(F)
        ports = jnp.maximum(ops.routes, 0)                       # (F, H)
        pocc = ctx.port_occ[ports]                               # (F, H)
        congested = (is_tau & (st.f_cnt > 0) & (ops.routes >= 0)
                     & (pocc > pc.sfc_threshold))                # (F, H)
        dur = jnp.clip(pocc, 1, pc.sfc_max_pause)                # (F, H)
        # upstream wire distance: hop h's switch is h links from the NIC
        delay = jnp.arange(H, dtype=I32) * topo.prop_ticks + 1   # (H,)
        slot = (ctx.t + delay) % env.RING                        # (H,)
        sfc_ring = sfc_ring.at[
            jnp.broadcast_to(slot[None, :], (F, H)),
            jnp.where(congested, f_ar[:, None], F)].max(dur)
        n_sfc = congested.sum().astype(I32)

    return ctx._replace(bloom_counts=bloom_counts, bloom_mid=bloom_mid,
                        bloom_rx=bloom_rx, pl=pl, pl_head=pl_head,
                        f_paused=f_paused, sfc_ring=sfc_ring, n_sfc=n_sfc)


def _pop_resumes(env: PhaseEnv, st, ops, do_pop, pl_head, f_paused,
                 bloom_counts):
    """Pop the head of each pause list that `do_pop` marks: clear that
    flow's pause bit and take it out of the upstream counting Bloom filter.
    The popping queues are taken RESUME_CHUNK at a time, so a pass costs
    the pops and not the fabric's P x Q queues."""
    P, Q, F, PLCAP = env.P, env.Q, env.F, env.PLCAP
    rank = jnp.cumsum(do_pop.reshape(-1).astype(I32))           # (P*Q,)
    n_pop = rank[-1]
    K = min(RESUME_CHUNK, P * Q)

    def chunk(c):
        # the loop body traces outside the caller's scope
        with jax.named_scope("control.resume"):
            start, f_paused, bloom_counts = c
            k = start + jnp.arange(K, dtype=I32)
            # the (k+1)-th popping queue: the count of ranks <= k
            e = jnp.minimum((rank[None, :] <= k[:, None]).sum(1, dtype=I32),
                            P * Q - 1)                              # (K,)
            p, q = e // Q, e % Q
            cand = st.pl[p, q, pl_head[p, q] % PLCAP]
            cand_f = jnp.maximum(cand, 0)
            cand_hop = hop_of_port(ops.routes, cand_f, p)
            valid = ((k < n_pop) & (cand >= 0)
                     & (st.f_q[cand_f, cand_hop] == q)
                     & st.f_paused[cand_f, cand_hop]
                     & (st.f_cnt[cand_f, cand_hop] > 0))
            # unpause (scatter with OOB-drop for invalid entries)
            f_paused = f_paused.at[jnp.where(valid, cand_f, F),
                                   cand_hop].set(False)
            up_port = ops.routes[cand_f, jnp.maximum(cand_hop - 1, 0)]
            bloom_counts = bloom.add_batch(
                bloom_counts, jnp.maximum(up_port, 0), ops.fpos[cand_f],
                jnp.where(valid, -1, 0))
            return start + K, f_paused, bloom_counts

    _, f_paused, bloom_counts = jax.lax.while_loop(
        lambda c: c[0] < n_pop, chunk, (jnp.int32(0), f_paused, bloom_counts))
    return pl_head + do_pop.astype(I32), f_paused, bloom_counts
