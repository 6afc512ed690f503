"""The BFC control law (§3.3.2) in abstract units, shared by the
pipeline-parallel scheduler, the serving admission controller and the data
pipeline. The packet simulator runs at mu = 1 packet per tick and uses the
integer form of the same threshold, `kernels.bfc_step.ref.pause_threshold`.

Everything is expressed in abstract units:
  * ``hrtt``       -- one hop round-trip (ticks / seconds / scheduler steps)
  * ``tau``        -- signalling interval (pause-frame period), paper: 0.5*hrtt
  * ``mu``         -- egress service rate (packets per tick / tokens per step)
  * ``n_active``   -- number of active (non-paused, backlogged) queues

The pause threshold is the minimum buffering that keeps the egress busy
through one pause/resume latency at the queue's fair-share drain rate:

    Th = (hrtt + tau) * mu / max(n_active, 1)
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class BackpressureParams:
    hrtt: float                 # one-hop RTT in control units
    tau: float                  # signalling period; paper uses 0.5 * hrtt
    mu: float = 1.0             # egress rate in packets per control unit
    resumes_per_interval: int = 1  # one resume per tau = two per HRTT (§3.3.2)

    @property
    def pause_window(self) -> float:
        return self.hrtt + self.tau


def pause_threshold(params: BackpressureParams, n_active) -> jnp.ndarray:
    """Th = (HRTT + tau) * (mu / N_active), in packets. ceil'd, >= 1."""
    n = jnp.maximum(jnp.asarray(n_active), 1)
    th = jnp.ceil(params.pause_window * params.mu / n)
    return jnp.maximum(th, 1.0).astype(jnp.int32)


def should_pause(queue_len, th) -> jnp.ndarray:
    """Pause the flow whose arrival pushed its queue past the threshold."""
    return jnp.asarray(queue_len) > jnp.asarray(th)


def should_resume(queue_len, th) -> jnp.ndarray:
    """Re-enable once the queue drains below the same threshold."""
    return jnp.asarray(queue_len) < jnp.asarray(th)


def worst_case_buffer(params: BackpressureParams, n_active) -> jnp.ndarray:
    """Upper bound on per-queue buffering: Th + (HRTT+tau)*mu (§3.3.2).

    With the <=2-resumes-per-HRTT rule this is ~2 one-hop BDPs (Fig. 20).
    """
    return pause_threshold(params, n_active) + jnp.int32(
        jnp.ceil(params.pause_window * params.mu))
