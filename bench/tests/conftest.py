"""Shared set-up for the benchmark's tests: they run on the CPU at small
sizes, with the benchmark directory and the program importable."""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# A small fabric and mix that keep every mechanism busy: 32 servers, 4
# ToRs, 2 spines, heavy load and incast, so that queues fill, pauses and
# drops happen within a few hundred ticks.
SMALL_FABRIC = dict(n_servers=32, n_tor=4, n_spine=2)
SMALL_TRAFFIC = dict(background_flows=400, load=0.9, incast_degree=20,
                     incast_total_kb=4000, incast_load=0.2,
                     flows_padded_to=512, n_ticks=400)


def small_config(name: str) -> dict:
    """A configuration at a small size: the leaf-spine (the default
    module) cut to SMALL_FABRIC; one that names its own module as it is."""
    doc = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    if "module" not in doc:
        doc["fabric"] = dict(doc["fabric"], **SMALL_FABRIC,
                             switch_buffer_pkts=1200)
    return doc


def small_traffic(name: str, **over) -> dict:
    doc = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    return {**doc, **SMALL_TRAFFIC, **over}


@pytest.fixture
def small_cell(monkeypatch):
    """Point the harness at a small copy of a cell and at the CPU."""
    import harness
    import jax

    def use(cell_name: str, **traffic_over):
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
        config = small_config(cell["config"])
        traffic = small_traffic(cell["traffic"], **traffic_over)
        monkeypatch.setattr(harness, "resolve",
                            lambda name: (bench, cell, config, traffic))
        monkeypatch.setattr(harness, "check_device",
                            lambda chips, peaks: jax.devices()[:1] * chips)
        return bench, cell, config, traffic
    return use
