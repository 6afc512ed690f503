"""The program's own names in a profiler trace: device time by phase, and
host time by stage.

The program wraps each phase of its tick in a `jax.named_scope`
(`phase.derive` ... `phase.stats`, with `switch_decision` nested in two of
them) and the runner's own work in `runner.quiescent`,
`runner.emit_write` and `runner.tail`. The scopes reach the optimized HLO
as metadata, not the trace: a TPU `XLA Ops` event names its instruction
(`%fusion.713 = ...`) and carries no op_name, so each event is mapped to
its instruction's scope path in the compiled program (`op_names`). Around
each host stage of a sweep call the program records a `repro.*` span, a
profiler `TraceAnnotation` with the stage's counts as stats, and keeps the
same spans in memory (`repro.sim.exec.dispatch.last_spans`).

    python3 bench/scopes.py --workload <cell> --seed <n> [--slice <path>]

runs a cell's set-up and one call under the profiler on the chip, and
prints as its last line one JSON object: device time per lane-tick of each
phase and runner scope, the share of device time the scopes cover, idle
time in gaps of 50 us or more by the innermost `repro.*` span over each
gap, the self time of each span name, and the traced and untraced walls
of a call. `--slice` writes 4 ms of the trace for `tests/`.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import shutil
import sys
import time
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import devtrace
import switch_decision

PHASES = ("derive", "control", "switch_tx", "nic_tx", "arrivals",
          "feedback", "stats")
SPAN_PREFIX = "repro."
# a scope inside an op_name path; under `vmap` a scope that comes first
# reads `vmap(runner.tail)`
SCOPE = re.compile(r"(?<![\w.])((?:phase|runner)\.\w+)")
OUTSIDE = "outside repro spans"
RUN_GRID = "repro.sweep.run_grid"


class Span(NamedTuple):
    """A host span of the program, with the counts it carries."""
    start_ns: float
    end_ns: float
    name: str
    stats: Dict[str, object]


def load(trace_dir: str) -> Tuple[List[Span], List[str]]:
    """(the `repro.*` host spans, the stat names the device ops carry) of
    the newest `.xplane.pb` under `trace_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    spans: List[Span] = []
    stats: set = set()
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    for e in line.events:
                        stats.update(dict(e.stats))
                        break
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(e.start_ns, e.end_ns, e.name,
                                  dict(e.stats))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return spans, sorted(stats)


_TABLE = re.compile(r"^(\d+) (.*)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path in an optimized HLO module's text.

    A path is the instruction's op_name under the function name of its
    stack frame: where JAX's locations keep one source frame
    (`jax_include_full_tracebacks_in_locations` off, as the program's
    compile cache sets it), the named scopes land in the frame and op_name
    keeps only the primitive. An instruction that names no scope, such as
    a fusion that XLA gave no metadata, takes the scope most of the
    instructions it calls name."""
    tables: Dict[str, Dict[int, str]] = {}
    table = None
    comps: Dict[str, List[str]] = defaultdict(list)
    comp = ""
    own: Dict[str, str] = {}
    frames: Dict[str, int] = {}
    calls: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = tables.setdefault(line, {})
            continue
        m = _TABLE.match(line) if table is not None else None
        if m:
            table[int(m.group(1))] = m.group(2)
            continue
        table = None
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split(" ")[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if m:
            name, rest = m.groups()
            comps[comp].append(name)
            op = re.search(r'op_name="([^"]*)"', rest)
            own[name] = op.group(1) if op else ""
            frame = re.search(r"stack_frame_id=(\d+)", rest)
            frames[name] = int(frame.group(1)) if frame else 0
            calls[name] = _CALLS.findall(rest)
    for name, frame in frames.items():
        if frame:
            own[name] = f"{_frame_name(tables, frame)}/{own[name]}"

    def scope_in(comp_name: str, depth: int = 0) -> List[str]:
        found = []
        for n in comps.get(comp_name, []):
            sc = scope_of(own[n])
            if sc:
                found.append(sc)
            elif depth < 4:
                for c in calls[n]:
                    found.extend(scope_in(c, depth + 1))
        return found

    out = {}
    for name, path in own.items():
        if not scope_of(path) and calls[name]:
            found = [sc for c in calls[name] for sc in scope_in(c)]
            if found:
                path = f"{Counter(found).most_common(1)[0][0]}/{path}"
        out[name] = path
    return out


def _frame_name(tables: Dict[str, Dict[int, str]], frame: int) -> str:
    """The function name of a stack frame in an HLO module's tables."""
    try:
        loc = re.search(r"file_location_id=(\d+)",
                        tables["StackFrames"][frame]).group(1)
        fn = re.search(r"function_name_id=(\d+)",
                       tables["FileLocations"][int(loc)]).group(1)
        return tables["FunctionNames"][int(fn)].strip('"')
    except (KeyError, AttributeError):
        return ""


def scopes_from_hlo(rec: devtrace.Recording, hlo_text: str
                    ) -> Dict[str, str]:
    """Each device event's scope path, by its instruction's name in the
    program that ran."""
    names = op_names(hlo_text)
    return {e.name: names.get(e.name.split(" = ")[0].lstrip("%"), "")
            for evs in rec.devices.values() for e in evs}


def scope_of(path: str) -> str:
    """The phase an op's path names (nested scopes count toward their
    phase), else its runner scope, else ''."""
    names = SCOPE.findall(path)
    phase = [n for n in names if n.startswith("phase.")]
    return phase[0] if phase else (names[0] if names else "")


def device_seconds(rec: devtrace.Recording, scopes: Dict[str, str],
                   devices: Sequence[int],
                   window: Optional[Tuple[float, float]] = None
                   ) -> Dict[str, float]:
    """Leaf-op device seconds inside `window` (the benchmark's span where
    None), summed over `devices`, by `scope_of` each op's path ('' for an
    op under no scope)."""
    lo, hi = window if window is not None else devtrace.window_of(rec)
    out: Dict[str, float] = defaultdict(float)
    for dev in devices:
        for e in devtrace.clip(devtrace.leaves(rec.devices.get(dev, [])),
                               lo, hi):
            out[scope_of(scopes.get(e.name, ""))] += (e.end_ns
                                                      - e.start_ns) / 1e9
    return dict(out)


def phase_us_per_lane_tick(seconds: Dict[str, float], lane_ticks: int
                           ) -> Optional[Dict[str, float]]:
    """Each phase's device microseconds per lane-tick (0.0 for a phase
    that ran no op); None where no op carries any phase scope."""
    if not any(k.startswith("phase.") for k in seconds):
        return None
    return {p: seconds.get(f"phase.{p}", 0.0) * 1e6 / lane_ticks
            for p in PHASES}


def covered_share(seconds: Dict[str, float]) -> float:
    """The share of leaf-op device time under some phase or runner
    scope."""
    total = sum(seconds.values())
    return (total - seconds.get("", 0.0)) / total if total else 0.0


def self_seconds(spans: Sequence[Span]) -> Dict[str, float]:
    """Seconds each span name spent outside the spans nested in it, summed
    over its spans. Nesting is read from the intervals: the spans of one
    call come from one thread."""
    out: Dict[str, float] = defaultdict(float)
    open_: List[Span] = []
    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        while open_ and open_[-1].end_ns <= s.start_ns:
            open_.pop()
        dur = (s.end_ns - s.start_ns) / 1e9
        out[s.name] += dur
        if open_ and s.end_ns <= open_[-1].end_ns:
            out[open_[-1].name] -= dur
        open_.append(s)
    return dict(out)


def lanes_of(spans: Sequence[Span]) -> Optional[int]:
    """The lanes of the sweep calls among `spans`."""
    calls = [s for s in spans if s.name == RUN_GRID]
    return sum(int(s.stats["lanes"]) for s in calls) if calls else None


def ms_per_lane(spans: Sequence[Span], names: Sequence[str]
                ) -> Optional[float]:
    """Self milliseconds of the spans called `names`, over the lanes of
    the sweep calls they belong to; None without a sweep call."""
    lanes = lanes_of(spans)
    if not lanes:
        return None
    own = self_seconds(spans)
    return sum(own.get(n, 0.0) for n in names) * 1e3 / lanes


def last_call_spans() -> Optional[List[Span]]:
    """The program's in-memory span record of its last top-level call;
    None where the program keeps no such record."""
    from repro.sim.exec import dispatch
    last = getattr(dispatch, "last_spans", None)
    if last is None:
        return None
    return [Span(s.start_ns, s.start_ns + s.dur_ns, s.name, dict(s.counts))
            for s in last()]


def idle_by_span(rec: devtrace.Recording, spans: Sequence[Span],
                 devices: Sequence[int],
                 window: Optional[Tuple[float, float]] = None,
                 min_gap_ns: float = devtrace.SHORT_GAP_NS
                 ) -> Dict[str, float]:
    """Idle seconds in gaps of `min_gap_ns` or more between device ops,
    summed over `devices`, by the innermost `repro.*` span covering each
    gap's middle (`OUTSIDE` for a gap under none)."""
    lo, hi = window if window is not None else devtrace.window_of(rec)
    host = [devtrace.Event(s.start_ns, s.end_ns, s.name) for s in spans
            if s.end_ns > lo and s.start_ns < hi]
    out: Dict[str, float] = defaultdict(float)
    for dev in devices:
        merged = devtrace.union_intervals(devtrace.clip(
            devtrace.leaves(rec.devices.get(dev, [])), lo, hi))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                if b - a >= min_gap_ns]
        whos = devtrace._innermost(host, [(a + b) / 2 for a, b in gaps])
        for (a, b), who in zip(gaps, whos):
            out[OUTSIDE if who == "no host span" else who] += (b - a) / 1e9
    return dict(out)


def report(rec: devtrace.Recording, scopes: Dict[str, str],
           spans: Sequence[Span], devices: Sequence[int],
           lane_ticks: int) -> dict:
    """Everything `main` prints about one traced call."""
    lo, hi = devtrace.window_of(rec)
    call = [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]
    secs = device_seconds(rec, scopes, devices)
    busy = sum(devtrace.reduce(rec, switch_decision.KERNEL, devices)
               .busy_s.values())
    idle = idle_by_span(rec, spans, devices)
    ops: Dict[Tuple[str, str], float] = defaultdict(float)
    for dev in devices:
        for e in devtrace.clip(devtrace.leaves(rec.devices.get(dev, [])),
                               lo, hi):
            ops[devtrace.short_name(e.name),
                scope_of(scopes.get(e.name, ""))] += e.end_ns - e.start_ns
    return {
        "phase_us_per_lane_tick": phase_us_per_lane_tick(secs, lane_ticks),
        "scope_us_per_lane_tick": {k or "(none)": v * 1e6 / lane_ticks
                                   for k, v in sorted(secs.items())},
        "scope_share": covered_share(secs),
        "top_ops_us_per_lane_tick": [
            [op, sc, ns / 1e3 / lane_ticks] for (op, sc), ns in
            sorted(ops.items(), key=lambda kv: -kv[1])[:12]],
        "device_us_per_lane_tick": busy * 1e6 / lane_ticks,
        "idle_by_span_s": idle,
        "idle_under_spans": (1 - idle.get(OUTSIDE, 0.0) / sum(idle.values())
                             if idle else None),
        "span_self_s": self_seconds(call),
        "dispatch.stack_ms_per_lane": ms_per_lane(
            call, ["repro.dispatch.stack", "repro.dispatch.shard"]),
        "dispatch.readback_ms_per_lane": ms_per_lane(
            call, ["repro.dispatch.readback"]),
        "sweep.summarize_ms_per_lane": ms_per_lane(
            call, ["repro.sweep.summarize"]),
    }


def write_slice(path: str, rec: devtrace.Recording, scopes: Dict[str, str],
                spans: Sequence[Span], device: int, source: str,
                lane_ticks: int, width_ns: float = 4e6) -> None:
    """`width_ns` of one device's ops from the middle of the traced call,
    their paths, and every span of the call, for a test to read back."""
    lo, hi = devtrace.window_of(rec)
    mid = (lo + hi) / 2
    evs = [e for e in rec.devices.get(device, [])
           if e.end_ns > mid and e.start_ns < mid + width_ns]
    call = [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]
    doc = {"source": source, "window": [mid, mid + width_ns],
           "call_window": [lo, hi], "lane_ticks": lane_ticks,
           "devices": {str(device): [list(e) for e in evs]},
           "scopes": {e.name: scopes.get(e.name, "") for e in evs},
           "spans": [list(s) for s in call],
           # what the readers gave when the slice was recorded
           "device_s": device_seconds(rec, scopes, [device],
                                      (mid, mid + width_ns)),
           "span_self_s": self_seconds(call)}
    with gzip.open(path, "wt") as fh:
        json.dump(doc, fh)


def runner_hlo(cases) -> str:
    """The optimized HLO of the program the last sweep call ran."""
    from repro.sim import engine, sweep
    from repro.sim.exec import dispatch
    from repro.sim.topology import build_cached
    plan = dispatch.last_plan()
    cfg = cases[0][1]
    go = engine.compiled_runner(
        plan.dims, cfg, plan.f_max, plan.n_ticks, plan.unroll, batched=True,
        segment=plan.segment, early_exit=plan.early_exit,
        devices=plan.devices if plan.sharded else None)
    width = plan.chunk_width
    flows = [f for _, _, f in cases][:width]
    flows += [flows[0]] * (width - len(flows))
    topos = [build_cached(c.clos) for _, c, _ in cases][:width]
    topos += [topos[0]] * (width - len(topos))
    return go.lower(sweep.stack_operands(flows, cfg, plan.f_max),
                    sweep.stack_topos(topos, cfg, plan.dims)
                    ).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--slice", default="")
    args = ap.parse_args(argv)
    import harness
    bench, cell, config, traffic = harness.resolve(args.workload)
    peaks = harness.load_json(harness.HERE / "peaks.json")
    harness.import_program()
    import jax
    used = harness.check_device(cell["chips"], peaks)[:cell["chips"]]
    from repro import compile_cache
    from repro.sim import sweep
    compile_cache.enable()
    topo, cases, _ = harness.build_cases(config, traffic, args.seed)
    H = int(traffic["n_ticks"])

    def call():
        t0 = time.perf_counter()
        sweep.run_grid(topo, cases, n_ticks=H, devices=used,
                       pad_multiple=int(traffic["flows_padded_to"]))
        return time.perf_counter() - t0

    call()
    walls = [call() for _ in range(2)]
    trace_dir = harness.TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        traced = call()
    jax.profiler.stop_trace()
    rec = devtrace.load(str(trace_dir))
    spans, op_stats = load(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    scopes = scopes_from_hlo(rec, runner_hlo(cases))
    lane_ticks = len(cases) * H
    out = report(rec, scopes, spans, [d.id for d in used], lane_ticks)
    out.update(workload=args.workload, seed=args.seed,
               device=used[0].device_kind, untraced_walls_s=walls,
               traced_wall_s=traced, device_op_stats=op_stats)
    if args.slice:
        write_slice(args.slice, rec, scopes, spans, used[0].id,
                    f"{used[0].device_kind}, {args.workload}, 4 ms of one "
                    "traced call's device ops and every repro.* span of "
                    "the call", lane_ticks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    sys.exit(main())
