"""Benchmark runner: one function per paper table/figure + microbenches.
Prints ``name,metric,value`` CSV. Set BENCH_FULL=1 for paper-scale topology;
use --only substring to filter. ``--scenario NAME`` (or ``all``) runs any
entry of the experiment registry (repro.sim.scenarios) through the batched
sweep subsystem instead of the figure list, records the perf trajectory
into ``BENCH_sweep.json`` (merge-appended per scenario so it accumulates
across PRs; ``--bench-json`` to relocate, ``--spool-dir`` to also spool
per-chunk results, ``--resume`` to restart an interrupted spooled run
from its chunk journal), and ends with a one-line per-scenario summary table
reporting ``active_ticks``/``n_ticks`` from the quiescence early exit.
``--no-early-exit`` forces the flat scan; ``--flat-baseline`` times both
and records the speedup; ``--kernel-impl``/``--kernel-baseline`` pick (or
A/B) the switch-decision path and record per-path per-tick wall time;
``--long-lived-pkts`` shrinks the probe flow so smoke-scale
``table1_long_lived`` can drain; ``--trace`` captures every per-tick
trace channel and spools them for ``python -m repro.sim.replay``;
``--list-scenarios`` shows the registry."""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def run_scenarios(which: str, bench_json: str = "BENCH_sweep.json",
                  spool_dir: str = "", early_exit: bool = True,
                  flat_baseline: bool = False, kernel_impl: str = "",
                  kernel_baseline: bool = False, trace: bool = False,
                  resume: bool = False, **overrides) -> None:
    """Nightly mode: run registry scenarios through the exec-planned
    batched sweep and record the perf trajectory — each scenario reports
    its grid size, wall time, lanes/sec, device count, XLA trace delta
    (which must stay at the number of protocol variants, never scale with
    topologies/loads/degrees/seeds), and the active-horizon profile
    (max/mean `active_ticks` vs the padded `n_ticks`, plus the arrival
    phase's sorts-per-tick). `early_exit=False` (--no-early-exit) times
    the flat scan instead; `flat_baseline=True` (--flat-baseline) runs
    BOTH and records the measured speedup. `kernel_impl` forces the
    switch-decision path (sets REPRO_KERNEL for the run, see
    `kernels.bfc_step.ops`); `kernel_baseline=True` (--kernel-baseline)
    runs each scenario on BOTH the lax path and the kernel path
    (interpret on CPU, pallas on TPU via 'auto') and records per-path
    per-active-tick wall time under the `kernel_impl` column — which is
    recorded for EVERY scenario run (keyed by the RESOLVED decision path
    each execute call reported, not the flag), so single-path runs get
    the column too. `trace=True` (--trace) runs every case with
    `TraceSpec.full()` and spools the per-tick channels through the run
    store for `python -m repro.sim.replay`. The run store merge-appends
    it all into `BENCH_sweep.json` and the run ends with a per-scenario
    summary table plus the total `engine.trace_count()`. `resume=True`
    (--resume; requires --spool-dir, where the interrupted run's chunk
    journal lives) reuses every chunk the interrupted run already spooled
    and recomputes only the missing/corrupt rest — the merged results are
    bit-identical to an uninterrupted run (see `exec.resume`)."""
    import tempfile

    import jax
    import numpy as np

    from .common import emit, emit_fct_table, run_scenario
    from repro.kernels.bfc_step import ops as kernel_ops
    from repro.sim import engine, phases, scenarios
    from repro.sim import exec as exec_
    from repro.sim.exec import dispatch

    def timing_since(tmark: int) -> dict:
        """Aggregate dispatch.TIMING_LOG entries appended since `tmark`,
        grouped by the RESOLVED `kernel_impl` each execute call recorded —
        so every scenario run gets a correct per-path column, regardless
        of how the path was chosen (flag, REPRO_KERNEL, 'auto', or the
        scenario's own ProtoConfig)."""
        out: dict = {}
        for e in dispatch.TIMING_LOG.since(tmark):
            g = out.setdefault(e["kernel_impl"],
                               {"wall_s": 0.0, "active_ticks_total": 0})
            g["wall_s"] += e["wall_s"]
            g["active_ticks_total"] += int(e["active_ticks_total"])
        for g in out.values():
            g["wall_s"] = round(g["wall_s"], 3)
            g["tick_wall_us"] = round(
                g["wall_s"] * 1e6 / max(g["active_ticks_total"], 1), 3)
        return out

    if resume and not spool_dir:
        raise SystemExit("--resume needs --spool-dir: the interrupted "
                         "run's chunk journal lives there")
    # records-only runs root the store in a scratch dir: rooting at "."
    # would reattach any stale manifest.json lying in the cwd
    store = exec_.RunStore(spool_dir
                           or tempfile.mkdtemp(prefix="bench_store_"))
    if trace:
        from repro.sim.trace import TraceSpec
        overrides["trace"] = TraceSpec.full()
        print(f"# tracing {TraceSpec.full().describe()} -> {store.root} "
              f"(replay: python -m repro.sim.replay list {store.root})",
              flush=True)
    # traced runs must spool through the store even when records-only
    use_store = store if (spool_dir or trace) else None
    names = scenarios.names() if which == "all" else [which]
    grid_points = 0
    for name in names:
        print(f"# === scenario {name} ===", flush=True)
        t0 = time.time()
        before = engine.trace_count()
        mark = dispatch.ACTIVE_LOG.mark()
        tmark = dispatch.TIMING_LOG.mark()
        with kernel_ops.forced(kernel_impl):
            results = run_scenario(name, store=use_store,
                                   early_exit=early_exit, resume=resume,
                                   **overrides)
        wall = time.time() - t0
        kernel_timing = timing_since(tmark)
        compiles = engine.trace_count() - before
        grid_points += len(results)
        for r in results:
            emit_fct_table(r.label.replace("/", "_"), r.metrics)
            # grids with a centralized-oracle lane (protocol_zoo) report
            # each case's tail-latency distance from optimal
            if (r.metrics is not None
                    and r.metrics.distance_from_optimal is not None):
                emit(r.label.replace("/", "_"), "distance_from_optimal",
                     round(r.metrics.distance_from_optimal, 3))
        plan = exec_.last_plan()
        # active-horizon profile, aggregated over every protocol group the
        # scenario dispatched (one ACTIVE_LOG entry per execute call)
        landed = dispatch.ACTIVE_LOG.since(mark)
        active = (np.concatenate([a for _, a in landed])
                  if landed else np.zeros(0, np.int32))
        n_ticks = plan.n_ticks if plan else 0
        extras = {}
        if active.size:
            extras = {"active_ticks_max": int(active.max()),
                      "active_ticks_mean": round(float(active.mean()), 1),
                      "n_ticks": int(n_ticks)}
        if flat_baseline:
            t1 = time.time()
            run_scenario(name, early_exit=False, **overrides)
            flat_wall = time.time() - t1
            extras["flat_wall_s"] = round(flat_wall, 3)
            extras["speedup_vs_flat"] = round(flat_wall / max(wall, 1e-9),
                                              2)
        if kernel_baseline:
            # second pass on the other decision path: interpret-mode
            # kernel on CPU (the CI path), real pallas on TPU
            alt = ("pallas" if jax.devices()[0].platform == "tpu"
                   else "interpret")
            if alt not in kernel_timing:
                tmark2 = dispatch.TIMING_LOG.mark()
                print(f"# --- {name} kernel_impl={alt} pass ---",
                      flush=True)
                with kernel_ops.forced(alt):
                    run_scenario(name, early_exit=early_exit, **overrides)
                kernel_timing.update(timing_since(tmark2))
        extras["kernel_impl"] = kernel_timing
        rec = store.record_scenario(
            name, wall_s=wall, grid_points=len(results),
            xla_compilations=compiles,
            device_count=plan.n_devices if plan else 1,
            chunk_width=plan.chunk_width if plan else len(results),
            budget_source=plan.budget_source if plan else "unknown",
            early_exit=early_exit,
            sorts_per_tick=phases.SORTS_PER_TICK, **extras)
        emit(f"scenario_{name}", "grid_points", len(results))
        emit(f"scenario_{name}", "xla_compilations", compiles)
        emit(f"scenario_{name}", "wall_s", round(wall, 1))
        emit(f"scenario_{name}", "lanes_per_sec", rec["lanes_per_sec"])
        emit(f"scenario_{name}", "device_count", rec["device_count"])
        if active.size:
            emit(f"scenario_{name}", "active_ticks_max", int(active.max()))
            emit(f"scenario_{name}", "n_ticks", int(n_ticks))
            emit(f"scenario_{name}", "active_frac",
                 round(float(active.max()) / max(n_ticks, 1), 3))
        if "speedup_vs_flat" in extras:
            emit(f"scenario_{name}", "speedup_vs_flat",
                 extras["speedup_vs_flat"])
        for impl, tm in kernel_timing.items():
            if tm:
                emit(f"scenario_{name}", f"tick_wall_us_{impl}",
                     tm["tick_wall_us"])
    emit("scenarios", "grid_points_total", grid_points)
    emit("scenarios", "xla_compilations", engine.trace_count())
    emit("scenarios", "sorts_per_tick", phases.SORTS_PER_TICK)
    path = store.write_bench(bench_json,
                             platform=jax.devices()[0].platform,
                             device_count=len(jax.devices()))
    print(f"# wrote {path}", flush=True)
    print(store.summary_table(), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--skip-micro", action="store_true")
    ap.add_argument("--scenario", default="",
                    help="run one registry scenario (or 'all') through the "
                         "batched sweep instead of the figure list")
    ap.add_argument("--bench-json", default="BENCH_sweep.json",
                    help="where --scenario writes the perf-trajectory "
                         "record (default: ./BENCH_sweep.json)")
    ap.add_argument("--spool-dir", default="",
                    help="also spool every landed chunk's raw results "
                         "under DIR/chunks (off by default)")
    ap.add_argument("--n-flows", type=int, default=None,
                    help="override scenario flow count (smoke-test the "
                         "nightly at reduced scale)")
    ap.add_argument("--drain", type=int, default=None,
                    help="override post-horizon drain ticks")
    ap.add_argument("--long-lived-pkts", type=int, default=None,
                    help="override the long-lived flow size (smoke-scale "
                         "table1_long_lived: let the probe flow complete "
                         "so the drain goes quiescent)")
    ap.add_argument("--no-early-exit", action="store_true",
                    help="force the flat (non-segmented) runner — the "
                         "A/B escape hatch for the active-horizon early "
                         "exit")
    ap.add_argument("--flat-baseline", action="store_true",
                    help="additionally time each scenario on the flat "
                         "runner and record speedup_vs_flat in "
                         "BENCH_sweep.json")
    ap.add_argument("--kernel-impl", default="",
                    choices=["", "lax", "pallas", "interpret", "auto"],
                    help="force the switch-decision path for --scenario "
                         "runs (sets REPRO_KERNEL; see "
                         "docs/ARCHITECTURE.md 'Kernelized switch step')")
    ap.add_argument("--kernel-baseline", action="store_true",
                    help="run each scenario on both the lax and kernel "
                         "decision paths and record per-active-tick wall "
                         "time per path in BENCH_sweep.json's kernel_impl "
                         "column")
    ap.add_argument("--trace", action="store_true",
                    help="capture every trace channel (TraceSpec.full()) "
                         "for --scenario runs and spool the per-tick "
                         "channels through the run store (inspect with "
                         "python -m repro.sim.replay; use --spool-dir to "
                         "choose the store root)")
    ap.add_argument("--resume", nargs="?", const=True, default=False,
                    metavar="TAG",
                    help="resume an interrupted --scenario run from the "
                         "chunk journal under --spool-dir, recomputing "
                         "only missing/corrupt chunks (results are "
                         "bit-identical to an uninterrupted run); the "
                         "optional TAG names the scenario to resume when "
                         "--scenario is not given")
    ap.add_argument("--list-scenarios", action="store_true")
    args = ap.parse_args()
    if isinstance(args.resume, str) and not args.scenario:
        args.scenario = args.resume

    from . import common  # noqa: F401  (sys.path setup for repro)
    from repro import compile_cache
    compile_cache.enable()

    if args.list_scenarios:
        from repro.sim import scenarios
        for n in scenarios.names():
            print(f"{n}: {scenarios.get(n).description}")
        return
    if args.scenario:
        overrides = {k: v for k, v in
                     (("n_flows", args.n_flows), ("drain", args.drain),
                      ("long_lived_pkts", args.long_lived_pkts))
                     if v is not None}
        run_scenarios(args.scenario, bench_json=args.bench_json,
                      spool_dir=args.spool_dir,
                      early_exit=not args.no_early_exit,
                      flat_baseline=args.flat_baseline,
                      kernel_impl=args.kernel_impl,
                      kernel_baseline=args.kernel_baseline,
                      trace=args.trace, resume=bool(args.resume),
                      **overrides)
        return

    from . import paper_figs, micro
    benches = list(paper_figs.ALL) + ([] if args.skip_micro else
                                      list(micro.ALL))
    failures = 0
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        print(f"# === {fn.__name__} ===", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"# {fn.__name__} done in {time.time()-t0:.0f}s",
                  flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{fn.__name__},status,FAIL")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
