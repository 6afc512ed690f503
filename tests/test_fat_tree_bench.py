"""The benchmark's fat-tree configuration, judged against its reference:
`bfc_fattree_k8` cut to k = 4 (16 hosts, 20 switches) in a copy of the
tree, under a small Fig. 6 mix, runs through `harness.run` as the chip
runs it and is `correct` with `mismatch` 0 and `float_gap` 0, over ticks
in which BFC pauses."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.tier1

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
N_TICKS = 400
SEED = 4294967311
# a small, heavily loaded Fig. 6 mix (the benchmark's own small sizes)
SMALL_TRAFFIC = dict(background_flows=400, load=0.9, incast_degree=20,
                     incast_total_kb=4000, incast_load=0.2,
                     flows_padded_to=512, n_ticks=N_TICKS)

SCRIPT = """
import json, sys, jax
sys.path[:0] = ["bench", "src"]
import harness
harness.check_device = lambda chips, peaks: jax.devices()[:chips]
rc = harness.run(["--workload", "bfc_fattree_k8.fig6_small", "--seed",
                  sys.argv[1], "--seconds", "0.2"])
from repro.sim import sweep
bench, cell, config, traffic = harness.resolve(cell_name=
                                               "bfc_fattree_k8.fig6_small")
topo, cases, _ = harness.build_cases(config, traffic, int(sys.argv[1]))
res = sweep.run_grid(topo, cases, n_ticks=traffic["n_ticks"],
                     pad_multiple=traffic["flows_padded_to"])
print(json.dumps({"pauses": int(res[0].state.stat_pauses),
                  "hosts": topo.n_servers, "switches": topo.n_switches}))
sys.exit(rc)
"""


def test_fat_tree_k4_matches_the_reference(tmp_path):
    ignore = shutil.ignore_patterns(".work", "__pycache__", ".jax_cache")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=ignore)
    shutil.copytree(ROOT / "src", tmp_path / "src", ignore=ignore)
    cfg_path = tmp_path / "bench" / "configs" / "bfc_fattree_k8.json"
    config = json.loads(cfg_path.read_text())
    assert config["module"] == "fat_tree.py"
    config["fabric"]["k"] = 4
    cfg_path.write_text(json.dumps(config))
    traffic = json.loads((BENCH / "traffic" / "fig6_x1.json").read_text())
    (tmp_path / "bench" / "traffic" / "fig6_small.json").write_text(
        json.dumps({**traffic, **SMALL_TRAFFIC, "name": "fig6_small"}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "bfc_fattree_k8.fig6_x1")
    bench["workloads"].append(dict(cell, name="bfc_fattree_k8.fig6_small",
                                   traffic="fig6_small"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, "-c", SCRIPT, str(SEED)],
                         cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    res, run = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["mismatch"]["value"] == 0
    assert res["check"]["float_gap"]["value"] == 0
    assert res["check"]["calls_differ"]["value"] == 0
    assert (run["hosts"], run["switches"]) == (16, 20)
    assert run["pauses"] > 0
