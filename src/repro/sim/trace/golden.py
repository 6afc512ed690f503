"""Golden-trace regression fixtures: one tiny pinned traced run per
protocol family, committed under ``tests/fixtures/traces/``.

Each fixture is the full-channel (`TraceSpec.full()`) per-tick trace of
ONE lane of one `config.PRESETS` family on a pinned micro-case: a 4-switch
Clos, a fixed uniform+incast workload (the incast burst makes the pause /
source-signal machinery fire, so SFC/PFC traces are not trivially zero),
and a fixed horizon. `CASES` pins each micro-case by name (its fabric, the
families pinned on it, its fixture directory under ``tests/fixtures/``):
``leaf_spine``, every family on the Clos above, and ``fat_tree``, BFC on a
k = 4 fat-tree (16 hosts, 20 switches, 6-hop routes) under the same
workload. The simulator is deterministic, so re-running the
same family must reproduce the committed trace bit-for-bit — the tier-1
test (`tests/test_golden_traces.py`) re-runs every family and asserts
``replay diff --expect same`` against its fixture, which turns any
unintended behavioural drift in any protocol's law into a first-divergence
tick report instead of a silent metrics shift.

Split of responsibilities with ``scripts/gen_golden_traces.py``:

* this module owns the pinned cases (`CASES`, `golden_case` /
  `golden_cfg` / `GOLDEN_N_TICKS`), fixture IO (`save_fixture` / `load_fixture`), the
  structural freshness check (`check_fixtures` — every family has a
  fixture, no orphans, pinned params and channel layout match the code),
  and `materialize`, which spools a loaded fixture into a `RunStore` as a
  synthetic traced run so the stock replay/diff CLI can compare it against
  a live re-run;
* the script is the thin regen/--check CLI over these functions.

Exec-layer imports (`RunStore`) stay function-local, mirroring
`trace.replay`, so importing `repro.sim.trace` never pulls the exec layer.
"""
from __future__ import annotations

import json
import tempfile
from dataclasses import asdict
from pathlib import Path
from typing import List, NamedTuple, Optional, Tuple, Union

import numpy as np

from .spec import EMIT_BASE, TraceSpec, layout

# pinned micro-cases: every fixture is one lane of one of these cases ----------
FIXTURE_VERSION = 1
GOLDEN_N_FLOWS = 24
GOLDEN_N_TICKS = 2048      # covers the incast drain; 4 engine segments
GOLDEN_SPEC = TraceSpec.full()

# repo-committed fixture root (tests/fixtures/ from repo root)
FIXTURE_ROOT = Path(__file__).resolve().parents[4] / "tests" / "fixtures"


class Case(NamedTuple):
    """One pinned micro-case."""
    fabric: str                        # a params class of sim/topology.py
    params: dict
    families: Optional[Tuple[str, ...]]  # None: every PRESETS family
    directory: str                     # its fixtures, under FIXTURE_ROOT


CASES = {
    "leaf_spine": Case("ClosParams", dict(n_servers=8, n_tor=2, n_spine=2,
                                          switch_buffer_pkts=512),
                       None, "traces"),
    "fat_tree": Case("FatTreeParams", dict(k=4, switch_buffer_pkts=512),
                     ("bfc",), "traces_fat_tree"),
}
DEFAULT_CASE = "leaf_spine"


def _golden_clos(case: str = DEFAULT_CASE):
    from .. import topology
    c = CASES[case]
    return getattr(topology, c.fabric)(**c.params)


def families(case: str = DEFAULT_CASE) -> List[str]:
    """The PRESETS families pinned on `case`."""
    from ..config import PRESETS
    names = CASES[case].families
    return sorted(PRESETS if names is None else names)


def _golden_wp():
    from ..workload import WorkloadParams
    # mild incast rides on the uniform background so pause-plane channels
    # (PFC, SFC source signals) are exercised, not identically zero
    return WorkloadParams(workload="uniform", load=0.6, seed=11,
                          incast_load=0.15, incast_degree=6,
                          incast_total_kb=1024)


def golden_case(case: str = DEFAULT_CASE):
    """(topo, flows) of the pinned micro-case `case`."""
    from .. import topology, workload
    topo = topology.build(_golden_clos(case))
    flows = workload.generate(topo, _golden_wp(), n_flows=GOLDEN_N_FLOWS)
    return topo, flows


def golden_cfg(proto, case: str = DEFAULT_CASE):
    from ..config import SimConfig
    return SimConfig(proto=proto, clos=_golden_clos(case), probe_flow=0,
                     trace=GOLDEN_SPEC)


def golden_layout(case: str = DEFAULT_CASE):
    from ..topology import TopoDims
    topo, _ = golden_case(case)
    dims = TopoDims.of(topo)
    return layout(GOLDEN_SPEC, dims.n_ports, dims.n_switches)


def pinned_meta(case: str = DEFAULT_CASE) -> dict:
    """The JSON-able pin a fixture must match to be considered fresh."""
    return {
        "version": FIXTURE_VERSION,
        "clos": asdict(_golden_clos(case)),
        "workload": asdict(_golden_wp()),
        "n_flows": GOLDEN_N_FLOWS,
        "n_ticks": GOLDEN_N_TICKS,
        "trace": GOLDEN_SPEC.describe(),
        "layout": golden_layout(case).meta(),
    }


def fixture_path(name: str, root: Union[str, Path, None] = None,
                 case: str = DEFAULT_CASE) -> Path:
    return Path(root or FIXTURE_ROOT) / CASES[case].directory / f"{name}.npz"


# ---- generation / IO ---------------------------------------------------------

def generate_fixture(proto, case: str = DEFAULT_CASE) -> dict:
    """Run one family on the pinned case and return its fixture payload:
    {trace (1, T, C), emits (1, T, 3), active_ticks (1,), meta}."""
    from .. import sweep
    from ..exec.store import RunStore
    topo, flows = golden_case(case)
    with tempfile.TemporaryDirectory() as tmp:
        store = RunStore(tmp)
        sweep.run_batch(topo, [flows], golden_cfg(proto, case),
                        GOLDEN_N_TICKS, store=store)
        trace, lay, _, active = store.load_trace(proto.name)
        _, emits = store.load_tag(proto.name)
    meta = pinned_meta(case)
    assert lay.meta() == meta["layout"], \
        "spooled layout drifted from golden_layout()"
    return {"trace": np.asarray(trace, np.int32),
            "emits": np.asarray(emits, np.int32),
            "active_ticks": (np.asarray(active, np.int64)
                             if active is not None
                             else np.full(trace.shape[0], trace.shape[1],
                                          np.int64)),
            "meta": meta}


def save_fixture(path: Union[str, Path], fx: dict) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez_compressed(path, trace=fx["trace"], emits=fx["emits"],
                        active_ticks=fx["active_ticks"],
                        meta=np.array(json.dumps(fx["meta"])))
    return path


def load_fixture(path: Union[str, Path]) -> dict:
    with np.load(path) as z:
        return {"trace": np.asarray(z["trace"]),
                "emits": np.asarray(z["emits"]),
                "active_ticks": np.asarray(z["active_ticks"]),
                "meta": json.loads(str(z["meta"]))}


def materialize(store, tag: str, fx: dict) -> None:
    """Spool a loaded fixture into `store` as one synthetic traced run of
    `tag`, shaped exactly like a chunk `exec.dispatch` landed (npz with the
    emits + trace keys, manifest entry with lanes / active_ticks /
    trace_channels) — so `load_trace` and the replay/diff CLI read it with
    no special casing. The fixture carries no SimState, so `load_tag`
    (which reassembles state leaves) is not supported on a materialized
    tag; trace-level tooling never touches state."""
    from ..exec.store import _EMITS_KEY, _TRACE_KEY
    store.chunk_dir.mkdir(parents=True, exist_ok=True)
    run = max((e["run"] for e in store.manifest if e["tag"] == tag),
              default=-1) + 1
    path = store.chunk_dir / f"{len(store.manifest):04d}_{tag}_r{run}_c0.npz"
    np.savez(path, **{_EMITS_KEY: fx["emits"], _TRACE_KEY: fx["trace"]})
    store.manifest.append({
        "tag": tag, "run": run, "chunk": 0, "path": str(path),
        "lanes": int(fx["trace"].shape[0]),
        "active_ticks": [int(a) for a in fx["active_ticks"]],
        "trace_channels": fx["meta"]["layout"]})
    store.manifest_path.write_text(json.dumps(store.manifest, indent=1)
                                   + "\n")


# ---- structural freshness check (cheap, no simulation) -----------------------

def check_fixtures(root: Union[str, Path, None] = None,
                   case: Optional[str] = None) -> List[str]:
    """Problems that make the committed fixtures stale, as human-readable
    strings (empty list = structurally fresh), for every case in `CASES`
    or for `case` alone, under the fixture root `root` (default the
    committed one). Checks coverage (one fixture per family pinned on the
    case, no orphans) and that each fixture's pinned meta and array shapes
    match the code's current pinned case — i.e. everything short of
    re-simulating; bit-level identity is the tier-1 test's job."""
    if case is None:
        return [p for c in CASES for p in check_fixtures(root, c)]
    names = families(case)
    meta = pinned_meta(case)
    width = golden_layout(case).width
    problems: List[str] = []
    for name in names:
        path = fixture_path(name, root, case)
        if not path.exists():
            problems.append(f"{case}/{name}: missing fixture {path} "
                            "(run scripts/gen_golden_traces.py)")
            continue
        try:
            fx = load_fixture(path)
        except Exception as err:  # corrupt npz is a stale fixture too
            problems.append(f"{case}/{name}: unreadable fixture ({err!r})")
            continue
        if fx["meta"] != meta:
            drift = [k for k in meta if fx["meta"].get(k) != meta[k]]
            problems.append(
                f"{case}/{name}: pinned meta drifted (fields: {drift}; "
                "regenerate with scripts/gen_golden_traces.py)")
            continue
        want_tr = (1, GOLDEN_N_TICKS, width)
        want_em = (1, GOLDEN_N_TICKS, EMIT_BASE)
        if fx["trace"].shape != want_tr or fx["emits"].shape != want_em:
            problems.append(
                f"{case}/{name}: fixture shapes {fx['trace'].shape}/"
                f"{fx['emits'].shape} != pinned {want_tr}/{want_em}")
    known = {f"{n}.npz" for n in names}
    for p in sorted(fixture_path("", root, case).parent.glob("*.npz")):
        if p.name not in known:
            problems.append(f"orphan fixture {p} (no family of {case})")
    return problems
