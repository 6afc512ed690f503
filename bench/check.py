"""The comparison that decides `correct`: one lane of the timed path
against the reference (`reference.py`), leaf by leaf.

Two numbers per run, each with its limit (see PERF.md, "How correct is
decided"):

* `mismatch`: integer and boolean elements of the final state, emit rows
  and summary counters that differ from the reference, plus summary
  floats that differ at all. The simulator is exact: limit 0.
* `float_gap`: the worst float leaf's largest absolute difference,
  divided by that leaf's largest magnitude in the reference.

A third, `calls_differ`, counts window calls whose results differ from
the sampled call's (every call runs the same flows): limit 0.
"""
from __future__ import annotations

import hashlib
import math

import numpy as np

FLOAT_LEAVES = ("cwnd", "cwnd_ref", "rate", "rate_target", "tokens",
                "alpha", "tx_ewma", "u_ring")
METRIC_INTS = ("completed", "total", "buffer_max_pkts", "drops",
               "collisions", "allocs", "overflow", "pauses")
METRIC_FLOATS = ("fct_slowdown_avg", "fct_slowdown_p50", "fct_slowdown_p95",
                 "fct_slowdown_p99", "buffer_p99_pkts", "pfc_pause_frac")


def digest(results) -> str:
    """Content hash of every lane's final state and emit rows."""
    h = hashlib.sha256()
    for r in results:
        for name in r.state._fields:
            h.update(np.ascontiguousarray(getattr(r.state, name)).tobytes())
        h.update(np.ascontiguousarray(r.emits).tobytes())
    return h.hexdigest()


def _same_float(a: float, b: float) -> bool:
    return (math.isnan(a) and math.isnan(b)) or a == b


def compare_lane(state, emits, metrics, ref_state: dict, ref_emits,
                 ref_metrics: dict):
    """(mismatch, float_gap, {leaf: differing elements}) for one lane."""
    detail = {}
    gap = 0.0
    for name in state._fields:
        got = np.asarray(getattr(state, name))
        want = np.asarray(ref_state[name])
        if got.shape != want.shape:
            detail[name] = int(max(got.size, want.size))
            continue
        if name in FLOAT_LEAVES:
            g = got.astype(np.float64)
            w = want.astype(np.float64)
            scale = float(np.max(np.abs(w))) if w.size else 0.0
            diff = float(np.max(np.abs(g - w))) if w.size else 0.0
            if diff > 0:
                gap = max(gap, diff / scale if scale > 0 else math.inf)
            continue
        n = int((got.astype(np.int64) != want.astype(np.int64)).sum())
        if n:
            detail[name] = n
    e = int((np.asarray(emits, np.int64) != np.asarray(ref_emits)).sum()) \
        if np.shape(emits) == np.shape(ref_emits) else int(np.size(emits))
    if e:
        detail["emits"] = e
    for key in METRIC_INTS:
        if int(getattr(metrics, key)) != int(ref_metrics[key]):
            detail[f"metrics.{key}"] = 1
    for key in METRIC_FLOATS:
        if not _same_float(float(getattr(metrics, key)),
                           float(ref_metrics[key])):
            detail[f"metrics.{key}"] = 1
    return sum(detail.values()), gap, detail
