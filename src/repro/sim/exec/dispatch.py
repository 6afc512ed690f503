"""Multi-device dispatcher: stream an `ExecPlan`'s chunks through one
compiled executable.

Lanes of each chunk are sharded evenly across the plan's devices with a
batch-axis `NamedSharding`, and the ONE cached vmapped program is split
over them by `shard_map` (see `engine.compiled_runner`) — not per-device
jits, so the compile-count contract ("one XLA compilation per protocol
variant", `engine.trace_count`) survives multi-device execution. Each
device loops over its own lanes and no collective crosses devices, so a
sharded run is bit-identical to the serial single-device run.

Chunks are double-buffered: chunk i+1 is dispatched (JAX dispatch is
async) before chunk i is pulled back to host, so `jax.device_get` +
phantom-lane trimming + optional `RunStore` spooling of chunk i overlap
device compute of chunk i+1. `plan.pipeline_depth` bounds how many chunks
are in flight — and therefore device-resident — at once (depth 1 = fully
synchronous, depth 2 = classic double buffer; the planner already divided
the byte budget by this depth, see `exec.planner`). Tail chunks are
padded with repeats of lane 0 so every dispatch reuses the one compiled
program; padded lanes are dropped at landing.

Each stage of a call (stack, shard, launch, wait, readback, spool, retry)
is a `span`: a profiler annotation and an entry in the call's in-memory
record, which `LAST_TIMING` is read from (docs/ARCHITECTURE.md,
"Observability").
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import engine
from ..engine import SimState
from ..trace import TraceLayout, layout as trace_layout, split_emits
from . import faults
from .faults import ExecError
from .planner import ExecPlan


class BoundedLog(list):
    """Append-only readback log bounded at `maxlen` entries: `append`
    drops the oldest overflow so a long-lived process never grows one
    without bound. Readers follow ONE take-a-mark-then-slice protocol
    (shared by `ACTIVE_LOG`, `TIMING_LOG`, and `TRACE_LOG` — don't copy it
    a fourth time): record ``mark = log.mark()`` before dispatching and
    slice ``log.since(mark)`` promptly after. Marks are *absolute*
    positions (total appends since process start), so a slow reader whose
    window was partially trimmed gets the surviving suffix rather than a
    misaligned slice."""

    def __init__(self, maxlen: int):
        super().__init__()
        self.maxlen = int(maxlen)
        self._dropped = 0        # entries trimmed away since process start

    def append(self, item) -> None:
        super().append(item)
        overflow = len(self) - self.maxlen
        if overflow > 0:
            del self[:overflow]
            self._dropped += overflow

    def mark(self) -> int:
        return self._dropped + len(self)

    def since(self, mark: int) -> list:
        return list(self[max(0, mark - self._dropped):])


@dataclass(eq=False)
class Span:
    """One host stage of a top-level call. `parent` is the index of the
    enclosing span in the call's record (None for the call itself);
    `dur_ns` is -1 while the span is open; `counts` are the work counts
    the stage carries (lanes, bytes, ...)."""
    name: str
    parent: Optional[int]
    start_ns: int
    dur_ns: int = -1
    counts: Dict[str, int] = field(default_factory=dict)


# The open top-level call's span record and stack of open spans, and the
# record of the most recent finished top-level call (one call, replaced
# by the next: not a log).
_OPEN = threading.local()
_CALL_IDS = itertools.count(1)
LAST_SPANS: List[Span] = []


@contextlib.contextmanager
def span(name: str, **counts: int):
    """Time one stage of the current top-level call: a profiler
    `TraceAnnotation(name, call=<id>, **counts)`, on the same clock as the
    device trace, and a `Span` appended to the call's in-memory record.
    The outermost span opens a new record and call id, which every span
    inside it shares. Use per chunk or per case, never per tick or flow."""
    global LAST_SPANS
    if not getattr(_OPEN, "stack", None):
        _OPEN.record, _OPEN.stack, _OPEN.call = [], [], next(_CALL_IDS)
    record, stack = _OPEN.record, _OPEN.stack
    sp = Span(name, stack[-1] if stack else None, time.perf_counter_ns(),
              counts=counts)
    stack.append(len(record))
    record.append(sp)
    try:
        with jax.profiler.TraceAnnotation(name, call=_OPEN.call, **counts):
            yield sp
    finally:
        sp.dur_ns = time.perf_counter_ns() - sp.start_ns
        stack.pop()
        if not stack:
            LAST_SPANS = record


def last_spans() -> List[Span]:
    return LAST_SPANS


def self_seconds(record: Sequence[Span], root: Span) -> Dict[str, float]:
    """Seconds each span name spent outside its child spans, summed over
    `root` and every span under it."""
    top = next(i for i, sp in enumerate(record) if sp is root)
    under = {top}
    out: Dict[str, float] = defaultdict(float)
    for i in range(top, len(record)):
        sp = record[i]
        if i != top and sp.parent not in under:
            continue
        under.add(i)
        out[sp.name] += sp.dur_ns / 1e9
        if i != top:
            out[record[sp.parent].name] -= sp.dur_ns / 1e9
    return dict(out)


# The most recent plan `execute` ran — introspection hook for examples,
# benchmarks, and trace_guard (what did the planner decide?).
LAST_PLAN: Optional[ExecPlan] = None

# Per-lane active tick counts of the most recent `execute` call (the tick
# each lane actually simulated to before the engine's quiescence early
# exit reconstructed the rest in closed form; == plan.n_ticks when a lane
# never went quiescent or early exit was off). `ACTIVE_LOG` accumulates
# one (tag, actives) entry per execute call so multi-group drivers
# (run_grid, benchmarks) can aggregate across protocol variants; see
# `BoundedLog` for the bound and the reader protocol.
LAST_ACTIVE: Optional[np.ndarray] = None
ACTIVE_LOG_MAX = 4096
ACTIVE_LOG: BoundedLog = BoundedLog(ACTIVE_LOG_MAX)

# Wall-clock accounting of the most recent `execute` call, keyed by the
# resolved `ProtoConfig.kernel_impl` so lax-vs-kernel benchmark runs can
# report per-tick cost per decision path (`benchmarks.run` writes wall
# time per ACTIVE tick into BENCH_sweep.json's `kernel_impl` column).
# `wall_s` is the `repro.dispatch.execute` span: dispatch through landing
# (compile included on the first call for a config — take a warmup run
# first when isolating steady-state cost); `stages` splits it into the
# self seconds of each `repro.dispatch.*` span name (see `span`).
# `budget_source` and `devices` repeat the plan's; `out_devices` is the
# most devices one computed chunk's outputs spanned (0 when every chunk
# was reloaded or landed through the retry path).
LAST_TIMING: Optional[Dict] = None
TIMING_LOG: BoundedLog = BoundedLog(ACTIVE_LOG_MAX)

# Per-segment trace readback (`SimConfig.trace` enabled): each execute
# call appends one (tag, trace[K, T, C], TraceLayout) entry as its chunks
# land — the in-process mirror of what `RunStore.spool_chunk` writes to
# disk. Bounded much tighter than the scalar logs: a trace block is
# K*T*C int32s, not a tuple of scalars.
LAST_TRACE: Optional[Tuple[np.ndarray, TraceLayout]] = None
TRACE_LOG_MAX = 64
TRACE_LOG: BoundedLog = BoundedLog(TRACE_LOG_MAX)

# OOM-adaptive retry provenance: one entry per RESOURCE_EXHAUSTED event
# the dispatcher recovered from (or gave up on), carrying the chunk, the
# width it failed at, and the width the retry bisected to. A fault-free
# run appends NOTHING here — scripts/trace_guard.py asserts the log stays
# empty (and the compile count unchanged) when no faults are injected.
RETRY_LOG: BoundedLog = BoundedLog(ACTIVE_LOG_MAX)


def last_plan() -> Optional[ExecPlan]:
    return LAST_PLAN


def last_active_ticks() -> Optional[np.ndarray]:
    return LAST_ACTIVE


def last_timing() -> Optional[Dict]:
    return LAST_TIMING


def last_trace() -> Optional[Tuple[np.ndarray, TraceLayout]]:
    """(trace[K, T, C], layout) of the most recent traced `execute` call —
    None when the last call ran with tracing off."""
    return LAST_TRACE


def lane_sharding(devices: Sequence) -> NamedSharding:
    """Batch-axis sharding: lane k of a chunk lands on device k * D // W."""
    mesh = Mesh(np.asarray(devices), ("lanes",))
    return NamedSharding(mesh, PartitionSpec("lanes"))


def _shard_tree(tree, sharding: NamedSharding):
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding),
                                  tree)


def _land(st, emits, active, n_real: int
          ) -> Tuple[SimState, np.ndarray, np.ndarray]:
    """Pull one chunk to host and drop its padded lanes: wait until the
    device is done with this chunk (later chunks keep computing), then
    copy it back and trim."""
    out = (st, emits, active)
    with span("repro.dispatch.wait"):
        jax.block_until_ready(out)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(out))
    with span("repro.dispatch.readback", bytes=int(n_bytes)):
        st, emits, active = jax.device_get(out)
        st = SimState(**{name: np.asarray(leaf)[:n_real]
                         for name, leaf in st._asdict().items()})
        return st, np.asarray(emits)[:n_real], np.asarray(active)[:n_real]


def execute(plan: ExecPlan, topos: Sequence, flowsets: Sequence, cfg, *,
            store=None, tag: str = "run", collect: bool = True,
            resume: bool = False):
    """Run K lanes (workload `flowsets[k]` on fabric `topos[k]`) under one
    protocol config according to `plan`. Returns (batched SimState,
    emits[K, T, 3]) bit-identical to an unchunked single-device
    `sweep.run_batch`. Per-lane `active_ticks` from the engine's
    quiescence early exit land in `LAST_ACTIVE` / `ACTIVE_LOG` (and in the
    store manifest) rather than the return value, so existing callers keep
    their (state, emits) contract. Likewise with `cfg.trace` enabled: the
    captured channels are split off each landed chunk's emit rows into
    `LAST_TRACE` / `TRACE_LOG` (and spooled beside the chunk when a store
    is given), and the returned emits stay (K, T, 3). With a `RunStore`, each chunk's trimmed
    results are spooled to disk the moment it lands; `collect=False`
    (requires a store) additionally drops each chunk from host memory once
    spooled and returns None — the streaming mode for grids whose merged
    result would not fit on host (reassemble lazily via
    `store.load_tag(tag)`).

    Fault tolerance (docs/ARCHITECTURE.md "Fault tolerance & resume"):
    a chunk whose dispatch or landing raises RESOURCE_EXHAUSTED is re-run
    in narrower sub-chunks under `plan.retry`'s bounded budget (width
    bisection + exponential backoff, down to single-lane dispatches)
    before a structured `ExecError` naming the failing lanes surfaces;
    every recovery event is journaled in `RETRY_LOG`. With `resume=True`
    (requires a store; see `resume()`), chunks already journaled by an
    interrupted run of `tag` — present, content-hash-intact, and matching
    this plan's lane ranges — are reloaded from disk instead of
    recomputed, and only the missing/corrupt remainder is dispatched; the
    merged result is bit-identical to a from-scratch run because lanes are
    independent and the npz round-trip is exact.

    Dispatch through landing is one `repro.dispatch.execute` span; its
    stages (stack, shard, launch, wait, readback, spool, retry) are spans
    under it, and their self seconds land in `LAST_TIMING["stages"]`."""
    global LAST_PLAN, LAST_ACTIVE, LAST_TIMING, LAST_TRACE
    LAST_PLAN = plan
    if not collect and store is None:
        raise ValueError("collect=False discards results: pass a store")
    if resume and store is None:
        raise ValueError("resume=True reloads spooled chunks: pass a store")
    from .. import sweep  # deferred: sweep <-> exec call into each other

    K = len(flowsets)
    if len(topos) != K:
        raise ValueError(f"{len(topos)} topologies for {K} flowsets")
    if plan.n_lanes != K:
        raise ValueError(f"plan covers {plan.n_lanes} lanes, got {K}")
    W = plan.chunk_width
    if plan.sharded and W % plan.n_devices:
        raise ValueError(f"chunk width {W} not a multiple of "
                         f"{plan.n_devices} devices")

    def runner(devices=None):
        return engine.compiled_runner(
            plan.dims, engine.static_cfg(cfg), plan.f_max, plan.n_ticks,
            plan.unroll, batched=True, segment=plan.segment,
            early_exit=plan.early_exit, devices=devices)

    go = runner(plan.devices if plan.sharded else None)
    sharding = lane_sharding(plan.devices) if plan.sharded else None
    # trace channels ride the emit rows (see sim/trace/): split them off
    # at landing so callers keep the (K, T, 3) emits contract, spool them
    # next to the chunk, and mirror them in TRACE_LOG for in-process reads
    lay = trace_layout(cfg.trace, plan.dims.n_ports, plan.dims.n_switches)

    # the run an interrupted spool left behind, which reused AND
    # recomputed chunks both land into (None = no prior run: resume
    # degrades to a plain execute)
    resume_run = None
    if resume:
        runs = store.runs_of(tag)
        resume_run = runs[-1] if runs else None

    n_retries = 0
    n_reused = 0
    out_devices = 0     # most devices one landed chunk's outputs spanned

    def _stack(idx: int, lo: int, n_take: int, width: int):
        """Operand bundles for lanes [lo, lo+n_take), padded to `width`
        with repeats of lane 0 (padded results dropped at landing)."""
        with span("repro.dispatch.stack", lanes=n_take,
                  padded_lanes=width - n_take, chunk=idx):
            fsets = list(flowsets[lo:lo + n_take])
            fsets += [flowsets[0]] * (width - n_take)
            tps = list(topos[lo:lo + n_take])
            tps += [topos[0]] * (width - n_take)
            return (sweep.stack_operands(fsets, cfg, plan.f_max,
                                         plan.dims.hops),
                    sweep.stack_topos(tps, cfg, plan.dims))

    def launch(idx: int, lo: int, n_real: int):
        """Stack + (optionally) shard one planned-width chunk and launch
        it (async). Tail chunks are padded so every dispatch reuses the
        one compiled program."""
        ops, t_ops = _stack(idx, lo, n_real, W)
        if sharding is not None:
            with span("repro.dispatch.shard", chunk=idx):
                ops = _shard_tree(ops, sharding)
                t_ops = _shard_tree(t_ops, sharding)
        with span("repro.dispatch.launch", lanes=n_real,
                  padded_lanes=W - n_real, chunk=idx):
            return go(ops, t_ops)

    def retry_chunk(idx: int, lo: int, n_real: int,
                    err: BaseException) -> Tuple:
        """OOM recovery for one chunk: re-run its lanes in narrower
        sub-chunks (synchronous, unsharded — correctness over overlap on
        the recovery path), bisecting the width on every further OOM under
        `plan.retry`'s budget. Returns the chunk landed to host; raises a
        structured `ExecError` naming the unlanded lanes when the budget
        is spent or width-1 still OOMs."""
        with span("repro.dispatch.retry", lanes=n_real, chunk=idx):
            return _retry_chunk(idx, lo, n_real, err)

    def _retry_chunk(idx: int, lo: int, n_real: int,
                     err: BaseException) -> Tuple:
        nonlocal n_retries
        pol = plan.retry
        w = max(pol.min_width, min(W, n_real) // 2)
        n_retries += 1
        RETRY_LOG.append({"tag": tag, "chunk": idx, "event": "oom",
                          "width": W, "retry_width": w,
                          "error": str(err)[:200]})
        states, emit_parts, active_parts = [], [], []
        off = 0
        attempt = 0
        while off < n_real:
            if pol.backoff_s > 0:
                time.sleep(pol.backoff_for(attempt))
            n_take = min(w, n_real - off)
            try:
                faults.fire("chunk", idx)
                ops = _stack(idx, lo + off, n_take, w)
                with span("repro.dispatch.launch", lanes=n_take,
                          padded_lanes=w - n_take, chunk=idx):
                    out = runner()(*ops)
                st, em, ac = _land(*out, n_take)
            except Exception as err2:     # noqa: BLE001 — filtered below
                if not faults.is_oom(err2):
                    raise
                attempt += 1
                n_retries += 1
                if w <= pol.min_width or attempt >= pol.max_retries:
                    RETRY_LOG.append(
                        {"tag": tag, "chunk": idx, "event": "give_up",
                         "width": w, "attempt": attempt,
                         "error": str(err2)[:200]})
                    raise ExecError(
                        f"chunk OOM'd at width {w} after {attempt} "
                        f"retr{'y' if attempt == 1 else 'ies'} "
                        f"(budget {pol.max_retries}, min width "
                        f"{pol.min_width})",
                        tag=tag, chunk=idx, lanes=(lo + off, lo + n_real),
                        cause=err2) from err2
                new_w = max(pol.min_width, w // 2)
                RETRY_LOG.append(
                    {"tag": tag, "chunk": idx, "event": "bisect",
                     "width": w, "retry_width": new_w, "attempt": attempt,
                     "error": str(err2)[:200]})
                w = new_w
                continue
            states.append(st)
            emit_parts.append(em)
            active_parts.append(ac)
            off += n_take
        merged = SimState(**{
            name: np.concatenate([np.asarray(getattr(s, name))
                                  for s in states])
            for name in SimState._fields})
        return merged, np.concatenate(emit_parts), \
            np.concatenate(active_parts)

    def compute(idx: int, lo: int) -> Tuple:
        """One chunk, launched async on the happy path; an OOM at dispatch
        (or the injected `oom@chunkN` fault) drops to the synchronous
        retry path and returns already-landed host arrays."""
        n_real = min(W, K - lo)
        try:
            faults.fire("chunk", idx)
            return ("inflight", n_real) + tuple(launch(idx, lo, n_real))
        except Exception as err:          # noqa: BLE001 — filtered below
            if not faults.is_oom(err):
                raise
            return ("landed", n_real) + tuple(retry_chunk(idx, lo, n_real,
                                                          err))

    def reuse_chunk(idx: int, lo: int):
        """A verified journaled chunk of the interrupted run, or None when
        it must be recomputed (absent, quarantined, hash-mismatched, or
        spooled under a different lane range / horizon / trace layout)."""
        if resume_run is None:
            return None
        n_real = min(W, K - lo)
        entry = store.find_chunk(tag, resume_run, idx)
        if entry is None or entry.get("quarantined"):
            return None
        reason = store.verify_chunk(entry)
        if reason is not None:
            store.quarantine(entry, reason)
            return None
        if (entry["lanes"] != n_real or entry.get("lane_lo", lo) != lo
                or "active_ticks" not in entry):
            return None
        st, emits, trace = store.load_chunk_full(entry["path"])
        emits = np.asarray(emits)
        if emits.shape[:2] != (n_real, plan.n_ticks):
            return None
        if lay.width and (trace is None
                          or entry.get("trace_channels") != lay.meta()):
            return None
        active = np.asarray(entry["active_ticks"], np.int32)
        return st, emits, (np.asarray(trace) if lay.width else None), active

    chunks: List[Tuple[SimState, np.ndarray]] = []
    actives: List[np.ndarray] = []
    traces: List[np.ndarray] = []
    inflight: deque = deque()

    def land_ready(idx: int, lo: int, st, emits, active, trace=None,
                   spool: bool = True):
        """Account one host-side chunk (freshly landed or reloaded) in
        arrival order; fresh chunks are journaled through the store."""
        actives.append(active)
        if trace is None:
            emits, trace = split_emits(emits, lay)
        if lay.width:
            traces.append(trace)
        if spool and store is not None:
            with span("repro.dispatch.spool", lanes=len(active), chunk=idx):
                store.spool_chunk(tag, idx, st, emits, active_ticks=active,
                                  trace=trace if lay.width else None,
                                  trace_channels=lay.meta() if lay.width
                                  else None,
                                  run=resume_run, lane_lo=lo)
        if collect:
            chunks.append((st, emits))

    def land_oldest():
        nonlocal out_devices
        idx, lo, kind, n_real, st, emits, active = inflight.popleft()
        if kind == "inflight":
            out_devices = max(out_devices, len(emits.sharding.device_set))
            try:
                st, emits, active = _land(st, emits, active, n_real)
            except Exception as err:      # noqa: BLE001 — filtered below
                if not faults.is_oom(err):
                    raise
                # deferred OOM surfacing at readback: same recovery path
                st, emits, active = retry_chunk(idx, lo, n_real, err)
        land_ready(idx, lo, st, emits, active)

    with span("repro.dispatch.execute", lanes=K) as call:
        record = _OPEN.record
        for idx, lo in enumerate(range(0, K, W)):
            cached = reuse_chunk(idx, lo) if resume else None
            if cached is not None:
                # drain in-flight work first so chunks land in index order
                while inflight:
                    land_oldest()
                n_reused += 1
                st_c, em_c, tr_c, ac_c = cached
                land_ready(idx, lo, st_c, em_c, ac_c, trace=tr_c,
                           spool=False)
                continue
            inflight.append((idx, lo) + compute(idx, lo))
            if len(inflight) >= max(1, plan.pipeline_depth):
                land_oldest()
        while inflight:
            land_oldest()

    LAST_ACTIVE = np.concatenate(actives) if actives else np.zeros(0, np.int32)
    ACTIVE_LOG.append((tag, LAST_ACTIVE))
    if lay.width:
        LAST_TRACE = (np.concatenate(traces) if traces
                      else np.zeros((0, plan.n_ticks, lay.width), np.int32),
                      lay)
        TRACE_LOG.append((tag,) + LAST_TRACE)
    else:
        LAST_TRACE = None

    LAST_TIMING = {
        "tag": tag,
        "kernel_impl": engine.static_cfg(cfg).proto.kernel_impl,
        "wall_s": call.dur_ns / 1e9,
        "lanes": K,
        "n_ticks": plan.n_ticks,
        "active_ticks_total": int(LAST_ACTIVE.sum()),
        "retries": n_retries,
        "chunks_reused": n_reused,
        "budget_source": plan.budget_source,
        "devices": plan.n_devices,
        "out_devices": out_devices,
        "stages": self_seconds(record, call),
    }
    TIMING_LOG.append(LAST_TIMING)

    if not collect:
        return None
    if len(chunks) == 1:
        return chunks[0]
    merged = SimState(**{
        name: np.concatenate([np.asarray(getattr(st, name))
                              for st, _ in chunks])
        for name in SimState._fields})
    return merged, np.concatenate([em for _, em in chunks])


def resume(plan: ExecPlan, topos: Sequence, flowsets: Sequence, cfg,
           store, *, tag: str = "run", collect: bool = True):
    """Resume an interrupted `execute` from its chunk journal: chunks the
    crashed run already landed (verified by content hash against the
    RunStore manifest) are reloaded from disk, only the missing or corrupt
    remainder is recomputed (landing *inside* the same run number, so the
    repaired run reassembles normally via `store.load_tag`), and the
    merged (state, emits) is bit-identical to an uninterrupted run —
    asserted end-to-end by scripts/fault_guard.py. A store with no prior
    run of `tag` degrades to a plain `execute`. Call with the same plan /
    operands / config as the interrupted run; chunks journaled under a
    different lane partition or horizon fail verification and are simply
    recomputed."""
    return execute(plan, topos, flowsets, cfg, store=store, tag=tag,
                   collect=collect, resume=True)
