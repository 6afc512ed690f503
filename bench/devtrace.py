"""Reduce a profiler trace of one window call to device busy time, kernel
time, the heaviest device operations and the idle gaps.

The JAX profiler writes an `.xplane.pb`; `load` turns it into plain event
tuples so that the reduction itself (`reduce`) runs on recorded data too
(tests/data/). A device is a plane named `/device:TPU:<n>`; its operations
are the events of the line `XLA Ops`. Control-flow operations (a while
loop and its body's nested loop) span the operations they run, so only
leaf events, which contain no other event of the line, count as work.
Busy time is the union of the leaf intervals inside the window, on the
devices the run used (a device that ran nothing counts as idle).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window_call"
# idle gaps shorter than this are the launch gaps between a program's own
# operations; longer ones are attributed to what the host was doing
SHORT_GAP_NS = 50_000
SHORT_GAP_NAME = "gaps under 50 us between device ops"


class Event(NamedTuple):
    start_ns: float
    end_ns: float
    name: str


class Recording(NamedTuple):
    """What the reduction needs from a trace."""
    devices: Dict[int, List[Event]]   # device id -> XLA Ops events
    host: List[Event]                 # host-side python and annotation spans


def load(trace_dir: str) -> Recording:
    """Read the newest `.xplane.pb` under `trace_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.start_ns, e.end_ns, e.name)
                               for e in line.events)
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.start_ns, e.end_ns, e.name)
                            for e in line.events)
    return Recording(devices=devices, host=host)


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event (operations that do work)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start_ns < e.end_ns \
                and nxt.end_ns <= e.end_ns:
            continue
        out.append(e)
    return out


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [Event(max(e.start_ns, lo), min(e.end_ns, hi), e.name)
            for e in events if e.end_ns > lo and e.start_ns < hi]


def union_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e.end_ns))
        else:
            merged.append((e.start_ns, e.end_ns))
    return merged


def window_of(rec: Recording, span: str = WINDOW_SPAN) -> Tuple[float, float]:
    """The traced call's interval: the benchmark's own host span."""
    hits = [e for e in rec.host if e.name == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    e = max(hits, key=lambda e: e.end_ns - e.start_ns)
    return e.start_ns, e.end_ns


def short_name(name: str) -> str:
    """`%fusion.713 = pred[49152]{...} fusion(...)` -> `fusion.713
    pred[49152]`: the instruction and its result type."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head[:80]
    return f"{head} {rest.split('{')[0].split(' ')[0]}"[:80]


class Reduction(NamedTuple):
    window_s: float
    busy_s: Dict[int, float]             # per device, inside the window
    kernel_s: float                      # summed over devices
    device_ops: List[Tuple[str, float]]  # top leaf ops by summed seconds
    idle_gaps: List[Tuple[str, float]]   # idle seconds by host activity


def _innermost(host: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each time, the shortest host span that covers it."""
    if not host:
        return ["no host span"] * len(times)
    start = np.array([e.start_ns for e in host])
    end = np.array([e.end_ns for e in host])
    length = end - start
    out = []
    for t in times:
        covers = (start <= t) & (t < end)
        if covers.any():
            out.append(host[int(np.argmin(np.where(covers, length,
                                                   np.inf)))].name)
        else:
            out.append("no host span")
    return out


def reduce(rec: Recording, kernel: "re.Pattern[str]",
           devices: Sequence[int],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Reduction:
    """Busy, kernel and op time of `devices` inside `window` (the
    benchmark's span where None)."""
    lo, hi = window if window is not None else window_of(rec)
    busy: Dict[int, float] = {}
    kernel_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    host = [e for e in rec.host if e.end_ns > lo and e.start_ns < hi
            and e.name != WINDOW_SPAN]
    for dev in devices:
        evs = rec.devices.get(dev, [])
        work = clip(leaves(evs), lo, hi)
        merged = union_intervals(work)
        busy[dev] = sum(b - a for a, b in merged) / 1e9
        for e in work:
            op_ns[short_name(e.name)] += e.end_ns - e.start_ns
        kernel_ns += sum(e.end_ns - e.start_ns for e in clip(evs, lo, hi)
                         if kernel.search(e.name))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        long_gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            if b - a < SHORT_GAP_NS:
                gap_ns[SHORT_GAP_NAME] += b - a
            else:
                long_gaps.append((a, b))
        whos = _innermost(host, [(a + b) / 2 for a, b in long_gaps])
        for (a, b), who in zip(long_gaps, whos):
            gap_ns[who] += b - a
    n = max(len(busy), 1)
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy, kernel_s=kernel_ns / 1e9,
        device_ops=[(k, v / 1e9 / n) for k, v in ops],
        idle_gaps=[(k, v / 1e9 / n) for k, v in gaps])
