"""Readings that set the limits of `correct` for one cell (PERF.md, "How
correct is decided"), in one process on the chip:

    python3 bench/limits.py --workload <cell> --seeds 12 --control-seeds 3

For each of `--seeds` seeds from `--first-seed`: build the cell's lanes,
make one call of the timed path (after one warm call that compiles), and
compare the cell's checked lanes with the float32 reference. For each
control seed: run the reference in bfloat16 in the program's place and
compare it the same way. Each sound reading also records what the window exercised, per lane:
drops, BFC pauses and resumes (pause-list pushes and pops), queue
allocations and collisions, PFC's paused share, and the flows that had
arrived, finished and were still live at the window's end. Prints one
line per reading and, last, a JSON
object with the lower readings (largest of the sound runs) and the upper
ones (smallest of the control); writes it to chiprun_out/ as well.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def as_results(ref_runs):
    """Reference outputs shaped like the timed path's CaseResults."""
    out = []
    for st, emits, summary in ref_runs:
        state = types.SimpleNamespace(**st)
        state._fields = tuple(st)
        out.append(types.SimpleNamespace(
            state=state, emits=emits,
            metrics=types.SimpleNamespace(**summary)))
    return out


def exercised(res, flows, n_ticks: int) -> dict:
    """Per-lane counts of what one call's window went through."""
    out = {k: [] for k in ("drops", "pauses", "resumes", "allocs",
                           "collisions", "pfc_pause_frac", "arrived",
                           "finished", "live")}
    for r, f in zip(res, flows):
        m = r.metrics
        arrived = np.asarray(f["arrival_tick"]) < n_ticks
        done = np.asarray(r.state.done) >= 0
        for key in ("drops", "pauses", "allocs", "collisions"):
            out[key].append(int(getattr(m, key)))
        out["resumes"].append(int(np.asarray(r.state.pl_head).sum()))
        out["pfc_pause_frac"].append(float(m.pfc_pause_frac))
        out["arrived"].append(int(arrived.sum()))
        out["finished"].append(int(done.sum()))
        out["live"].append(int((arrived & ~done).sum()))
    return out


def control_readings(config, traffic, seed, rules, lanes):
    """(mismatch, float_gap) of the configuration's bfloat16 reference
    against its float32 reference."""
    import ml_dtypes

    mod = harness.module_of(config)
    fabric = mod.fabric(config)
    flows = [mod.generate(fabric, traffic, seed + i)
             for i in range(traffic["lanes"])]
    runs = harness.references(config, [flows[k] for k in lanes],
                              [traffic["n_ticks"]] * len(lanes), rules,
                              ml_dtypes.bfloat16)
    fake = dict(zip(lanes, as_results(runs)))
    return harness.judge(fake, flows, lanes, config, rules)[:2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = harness.resolve(args.workload)
    peaks = harness.load_json(harness.HERE / "peaks.json")
    harness.import_program()
    devs = harness.check_device(cell["chips"], peaks)
    from repro import compile_cache
    from repro.sim import sweep
    compile_cache.enable()
    rules = harness.float_rules(devs[0].device_kind)
    used = devs[:cell["chips"]]
    lanes = list(range(traffic["lanes"]))
    out = {"cell": args.workload, "sound": [], "control": []}
    for i in range(args.seeds):
        seed = args.first_seed + 1000 * i
        topo, cases, flows = harness.build_cases(config, traffic, seed)
        t0 = time.perf_counter()
        res = sweep.run_grid(topo, cases, n_ticks=traffic["n_ticks"],
                             pad_multiple=traffic["flows_padded_to"],
                             devices=used)
        t1 = time.perf_counter()
        mm, gap, det = harness.judge(res, flows, lanes, config, rules)
        t2 = time.perf_counter()
        out["sound"].append({"seed": seed, "mismatch": mm, "float_gap": gap,
                             "call_s": t1 - t0, "reference_s": t2 - t1,
                             **exercised(res, flows, traffic["n_ticks"])})
        print(json.dumps(out["sound"][-1]), flush=True)
        if det:
            print(f"  differences: {det}", flush=True)
    for i in range(args.control_seeds):
        seed = args.first_seed + 1000 * i
        t0 = time.perf_counter()
        mm, gap = control_readings(config, traffic, seed, rules, lanes)
        out["control"].append({"seed": seed, "mismatch": mm,
                               "float_gap": gap,
                               "seconds": time.perf_counter() - t0})
        print(json.dumps(out["control"][-1]), flush=True)
    for key in ("mismatch", "float_gap"):
        out[f"lower_{key}"] = max((r[key] for r in out["sound"]), default=None)
        out[f"upper_{key}"] = min((r[key] for r in out["control"]),
                                  default=None)
    dest = harness.ROOT / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / f"limits_{args.workload}.json").write_text(json.dumps(out))
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("sound", "control")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
