"""BFC's switch decision: its kernel's name in the trace, and its work
counted from what it reads and writes.

The decision takes, per port, the occupancy of its Q queues, their pause
bits, the round-robin pointer and whether the port is blocked (PFC or a
NIC port), and gives the pause threshold, the queue picked (with whether
one exists) and the occupancy after the pick. The count does not depend
on what implements the decision.

Bytes: int32 occupancy in and out (4 + 4 per queue), a byte per pause
bit; per port an int32 pointer, threshold and pick and a byte each for
blocked and can-send: 9 P Q + 14 P.

Operations per queue: active test (compare, and-not), active count,
round-robin key (subtract, modulo), eligibility (and), packed key
(multiply, add), masking select, min reduction, pick test (compare, and)
and occupancy update (subtract): 13. Per port: max(n, 1) and the ceiling
division (add, subtract, divide) of the threshold, the pick's modulo and
the can-send compare: 6. So 13 P Q + 6 P.
"""
from __future__ import annotations

import re

# The decision's kernel in the device trace: the Pallas kernel lowers to a
# Mosaic custom call named after its function, `%_fused.<n> = ...
# custom_call_target="tpu_custom_call"`, one event per tick.
KERNEL = re.compile(r'^%_fused(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def switch_decision_cost(n_ports: int, n_queues: int):
    """(bytes, operations) of one port-set decision (one lane, one tick)."""
    p, q = n_ports, n_queues
    return 9 * p * q + 14 * p, 13 * p * q + 6 * p


def least_seconds(n_bytes: float, n_ops: float, peaks: dict):
    """The least time the chip needs, and which bound sets it. Integer
    operations are held to the chip's highest published operation rate."""
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    t_ops = n_ops / max(peaks["flops_per_s"], peaks["int_ops_per_s"])
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
