"""Phase 5: feedback consumption + congestion-control law updates.

Drains this tick's row of the delayed feedback rings (ACKs, ECN echoes,
HPCC max-path-utilization, retransmit credits) and applies the configured
end-host law: DCTCP's alpha-EWMA window cut, HPCC's reference-window
utilization rule, DCQCN's rate decrease / additive-increase timers, or
FairQ's fair-share rate chase (the `u_ring` then carries the bottleneck's
active-flow count instead of HPCC's path utilization: the rate jumps down
to `1/n` immediately and EWMAs up toward it otherwise). BFC itself needs
none of this (cc='none'): the phase then only books ACKs and replays
dropped packets. Under `proto.source_signal` (SFC) the phase additionally
lands this tick's row of the `sfc_ring` pause-signal delay line into the
per-flow `sfc_until` deadline that gates `nic_tx`.

The feedback rings are delay lines of static length `env.RING`
(= `dims.hops * dims.prop_max + 2`, the worst case over a batch's lanes):
`arrivals` scatters at `(t + delay) % RING` with a delay derived from the
lane's *traced* `prop_ticks`, and this phase drains row `t % RING`, so an
entry lands exactly `delay` ticks after it was scheduled no matter how far
the ring was padded — which is why mixed-latency lanes share one program
bit-identically."""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from .ctx import PhaseEnv, StepCtx


class CCVars(NamedTuple):
    """The (F,)-shaped end-host congestion-control state `cc_laws` evolves.

    Split out of `SimState` so the engine's active-horizon runner can
    replay the exact per-tick law update over a skipped quiescent tail
    (zero feedback) without touching the rest of the state."""
    cwnd: jnp.ndarray
    cwnd_ref: jnp.ndarray
    rate: jnp.ndarray
    rate_target: jnp.ndarray
    alpha: jnp.ndarray
    ack_seen: jnp.ndarray
    mark_seen: jnp.ndarray
    cc_timer: jnp.ndarray
    since_dec: jnp.ndarray

    @classmethod
    def of_state(cls, st) -> "CCVars":
        return cls(cwnd=st.cwnd, cwnd_ref=st.cwnd_ref, rate=st.rate,
                   rate_target=st.rate_target, alpha=st.alpha,
                   ack_seen=st.ack_seen, mark_seen=st.mark_seen,
                   cc_timer=st.cc_timer, since_dec=st.since_dec)


def cc_laws(pc, tm, v: CCVars, acks_now, marks_now, u_now) -> CCVars:
    """One tick of the configured congestion-control law.

    The ONE code path for the epoch timers and window/rate updates: the
    live `feedback` phase calls it with this tick's drained feedback rows,
    and `engine`'s quiescent-tail loop calls it with zeros — bit-identity
    of the early-exit runner rests on both running these exact ops in this
    exact order (see docs/ARCHITECTURE.md, "Active-horizon execution")."""
    cwnd, cwnd_ref, alpha = v.cwnd, v.cwnd_ref, v.alpha
    ack_seen = v.ack_seen + acks_now
    mark_seen = v.mark_seen + marks_now
    cc_timer = v.cc_timer - 1
    rate, rate_target, since_dec = v.rate, v.rate_target, v.since_dec
    if pc.cc == "dctcp":
        epoch = cc_timer <= 0
        fracm = mark_seen.astype(jnp.float32) / jnp.maximum(ack_seen, 1)
        alpha = jnp.where(epoch,
                          (1 - pc.dctcp_g) * alpha + pc.dctcp_g * fracm,
                          alpha)
        cwnd = jnp.where(epoch & (mark_seen > 0),
                         cwnd * (1 - alpha / 2), cwnd)
        cwnd = jnp.where(epoch & (mark_seen == 0), cwnd + 1.0, cwnd)
        cwnd = jnp.clip(cwnd, 1.0, float(pc.window_init))
        ack_seen = jnp.where(epoch, 0, ack_seen)
        mark_seen = jnp.where(epoch, 0, mark_seen)
        cc_timer = jnp.where(epoch, tm.e2e_rtt_ticks, cc_timer)
    elif pc.cc == "hpcc":
        has_fb = acks_now > 0
        u_norm = jnp.maximum(u_now, 1e-3) / pc.hpcc_eta
        w_new = cwnd_ref / u_norm + pc.hpcc_wai
        cwnd = jnp.where(has_fb,
                         jnp.clip(w_new, 1.0, float(pc.window_init)), cwnd)
        epoch = cc_timer <= 0
        cwnd_ref = jnp.where(epoch, cwnd, cwnd_ref)
        cc_timer = jnp.where(epoch, tm.e2e_rtt_ticks, cc_timer)
    elif pc.cc == "dcqcn":
        epoch = cc_timer <= 0
        congested = mark_seen > 0
        rate_target = jnp.where(epoch & congested, rate, rate_target)
        rate = jnp.where(epoch & congested, rate * (1 - alpha / 2), rate)
        alpha = jnp.where(
            epoch,
            jnp.where(congested,
                      (1 - pc.dcqcn_alpha_g) * alpha + pc.dcqcn_alpha_g,
                      (1 - pc.dcqcn_alpha_g) * alpha),
            alpha)
        since_dec = jnp.where(epoch & congested, 0, since_dec + 1)
        inc = since_dec >= pc.dcqcn_timer
        rate = jnp.where(inc, (rate + rate_target) / 2, rate)
        rate_target = jnp.where(
            inc, jnp.minimum(rate_target + pc.dcqcn_rai, 1.0), rate_target)
        since_dec = jnp.where(inc, 0, since_dec)
        rate = jnp.clip(rate, 1e-3, 1.0)
        mark_seen = jnp.where(epoch, 0, mark_seen)
        ack_seen = jnp.where(epoch, 0, ack_seen)
        cc_timer = jnp.where(epoch, tm.e2e_rtt_ticks, cc_timer)
    elif pc.cc == "fairq":
        # u_now = max active-flow count over the path's links when the
        # delivered packet left; the fair share there is 1/n. Decreases
        # take effect immediately ("fast"), increases chase the share
        # with gain fairq_g ("fair") -- and with zero feedback (the
        # quiescent-tail replay) every op below is the identity, so the
        # early-exit runner stays bit-identical for free.
        has_fb = acks_now > 0
        share = jnp.clip(1.0 / jnp.maximum(u_now, 1.0),
                         pc.fairq_rate_min, 1.0)
        rate = jnp.where(has_fb,
                         jnp.where(share < rate, share,
                                   rate + pc.fairq_g * (share - rate)),
                         rate)
        rate = jnp.clip(rate, pc.fairq_rate_min, 1.0)

    return CCVars(cwnd=cwnd, cwnd_ref=cwnd_ref, rate=rate,
                  rate_target=rate_target, alpha=alpha, ack_seen=ack_seen,
                  mark_seen=mark_seen, cc_timer=cc_timer,
                  since_dec=since_dec)


def feedback(env: PhaseEnv, st, ops, topo, ctx: StepCtx) -> StepCtx:
    pc, tm = env.cfg.proto, env.cfg.timing
    t = ctx.t

    row = t % env.RING
    ack_ring, mark_ring, u_ring = ctx.ack_ring, ctx.mark_ring, ctx.u_ring
    acks_now = ack_ring[row]
    marks_now = mark_ring[row]
    u_now = u_ring[row]
    ack_ring = ack_ring.at[row].set(0)
    mark_ring = mark_ring.at[row].set(0)
    u_ring = u_ring.at[row].set(0.0)
    acked = st.acked + acks_now
    rrow = t % env.RRING
    retx_ring = ctx.retx_ring
    retx_now = retx_ring[rrow]
    retx_ring = retx_ring.at[rrow].set(0)
    rem_src = ctx.rem_src + retx_now
    sent = ctx.sent - retx_now

    v = cc_laws(pc, tm, CCVars.of_state(st), acks_now, marks_now, u_now)

    # SFC: land this tick's pause signals at the sources (max-combine)
    sfc_ring, sfc_until = ctx.sfc_ring, st.sfc_until
    if pc.source_signal:
        sig = sfc_ring[row]
        sfc_ring = sfc_ring.at[row].set(0)
        sfc_until = jnp.where(sig > 0,
                              jnp.maximum(sfc_until, t + sig), sfc_until)

    return ctx._replace(ack_ring=ack_ring, mark_ring=mark_ring,
                        u_ring=u_ring, retx_ring=retx_ring, acked=acked,
                        rem_src=rem_src, sent=sent, cwnd=v.cwnd,
                        cwnd_ref=v.cwnd_ref, alpha=v.alpha,
                        ack_seen=v.ack_seen, mark_seen=v.mark_seen,
                        cc_timer=v.cc_timer, rate=v.rate,
                        rate_target=v.rate_target, since_dec=v.since_dec,
                        sfc_ring=sfc_ring, sfc_until=sfc_until)
