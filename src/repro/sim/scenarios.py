"""Named registry of the paper's experiment grid (+ beyond-paper scenarios).

Each `Scenario` is a declarative grid over protocol x topology x load x
incast-degree x seed for one workload family. `cases()` expands a scenario
into (label, SimConfig, FlowSet) triples — each case's fabric rides in its
`SimConfig.clos` — that `sim.sweep.run_grid` executes with one compilation
per protocol variant (topology, degree, load, and seed all ride the vmap
batch axis); `run()` is the one-call driver.

Registry:
  fig5_load_sweep         Fig. 5/16: BFC vs DCTCP across 50-90% load.
  fig6_incast             Fig. 6/9: Google workload + 5% incast cross
                          traffic, all realizable schemes vs Ideal-FQ.
  table1_long_lived       Table 1: one long-lived flow vs variable cross
                          traffic (probe throughput + short-flow tail).
  websearch_tail          DCTCP WebSearch distribution at moderate/high
                          load: heavy-tailed sizes stress tail latency.
  fig17_incast_degree     Fig. 17: incast degree axis 4-64; queue
                          exhaustion separates flow- from dest-keyed BFC.
  oversub_sweep           Beyond-paper: 4:1 / 2:1 / 1:1 core
                          oversubscription — per-hop backpressure vs e2e
                          CC as the core thins (topology batch axis).
  buffer_sweep            Beyond-paper: shallow -> deep switch buffers;
                          BFC's margin grows as buffers shrink (topology
                          batch axis via `buffer_limit` operand).
  rack_local_skew         Beyond-paper: 70% rack-local traffic; tests that
                          backpressure does not penalize intra-rack flows
                          when the core is quiet.
  incast_plus_background  Beyond-paper: 10% incast on top of a 50-70%
                          loaded fabric, incl. BFC's per-dest variant
                          (queue exhaustion regime of Fig. 17).
  rtt_sweep               Beyond-paper: link delay 1-64 ticks as a batch
                          axis — each scheme's sensitivity to wire delay
                          it was not retuned for (timing constants stay
                          at the paper's prop=12 calibration; prop_ticks
                          is a traced operand, so every delay shares one
                          compilation per protocol).
  cross_dc_latency        Beyond-paper: long-haul link delays paired with
                          60% rack-local cross traffic; does backpressure
                          spare local flows when the far lanes are slow?
  protocol_zoo            Beyond-paper: every protocol family -- the
                          paper's roster plus SFC (arXiv 2305.00538),
                          FairQ (arXiv 2401.04850), and the centralized
                          SRPT oracle (arXiv 1710.02548) -- head-to-head
                          on the paper's three workload families; the
                          oracle lane annotates every case's metrics with
                          `distance_from_optimal`.

`docs/SCENARIOS.md` is the generated reference table of this registry
(`scripts/gen_scenario_docs.py`; CI fails if it drifts).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

from .config import PRESETS, SimConfig
from .topology import (ClosParams, FabricParams, FatTreeParams, Topology,
                       build, build_cached)


def topo_tag(clos: FabricParams) -> str:
    """Short label component identifying a fabric in multi-topology grids.

    Includes the link delay so fabrics that differ only in `prop_ticks`
    (the rtt_sweep / cross_dc_latency axes) still get distinct labels."""
    shape = (f"k{clos.k}" if isinstance(clos, FatTreeParams)
             else f"t{clos.n_tor}x{clos.n_spine}s{clos.n_servers}")
    return f"{shape}b{clos.switch_buffer_pkts}p{clos.prop_ticks}"


@dataclass(frozen=True)
class Scenario:
    name: str
    description: str
    # paper figure/table this grid reproduces; "" = beyond-paper scenario.
    # Surfaced by scripts/gen_scenario_docs.py into docs/SCENARIOS.md.
    paper_ref: str = ""
    workload: str = "fb_hadoop"
    # optional workload-family axis: each entry becomes its own batch lane
    # per (topology, load, seed, degree); empty = just `workload`.
    workloads: Tuple[str, ...] = ()
    protos: Tuple[str, ...] = ("bfc",)
    loads: Tuple[float, ...] = (0.6,)
    seeds: Tuple[int, ...] = (0,)
    n_flows: int = 1500
    incast_load: float = 0.0
    incast_degree: int = 20
    incast_total_kb: int = 4000
    # optional incast-degree axis (Fig. 17): overrides `incast_degree`, and
    # when `incast_kb_per_flow` > 0 each degree's event size scales with it
    # (aggregate = degree * kb_per_flow) so per-sender work stays constant.
    incast_degrees: Tuple[int, ...] = ()
    incast_kb_per_flow: int = 0
    # optional topology axis: each entry becomes a batch lane (padded to a
    # common TopoDims by sim.sweep); empty = the caller/driver's fabric.
    topologies: Tuple[FabricParams, ...] = ()
    locality: float = 0.0
    long_lived: int = 0
    long_lived_pkts: int = 1 << 24
    drain_ticks: int = 20_000

    def degree_axis(self) -> Tuple[int, ...]:
        return self.incast_degrees or (self.incast_degree,)

    def workload_axis(self) -> Tuple[str, ...]:
        return self.workloads or (self.workload,)

    def axes(self) -> Dict[str, int]:
        """Cardinality of every sweep axis (without generating workloads)."""
        return {"protos": len(self.protos), "loads": len(self.loads),
                "seeds": len(self.seeds), "degrees": len(self.degree_axis()),
                "workloads": len(self.workload_axis()),
                "topologies": max(1, len(self.topologies))}

    def grid_size(self) -> int:
        """Number of grid points `cases()` expands to (= batch lanes)."""
        n = 1
        for k in self.axes().values():
            n *= k
        return n

    def topology_axis(self, default: Optional[FabricParams]
                      ) -> Tuple[FabricParams, ...]:
        if self.topologies:
            return self.topologies
        return (default if default is not None else ClosParams(),)

    def grid(self) -> List[Tuple[str, float, int]]:
        return [(p, l, s) for p in self.protos for l in self.loads
                for s in self.seeds]

    def flowset(self, topo: Topology, load: float, seed: int,
                n_flows: Optional[int] = None,
                incast_degree: Optional[int] = None,
                long_lived_pkts: Optional[int] = None,
                workload: Optional[str] = None):
        from .workload import WorkloadParams, generate
        degree = (incast_degree if incast_degree is not None
                  else self.incast_degree)
        total_kb = self.incast_total_kb
        if self.incast_kb_per_flow > 0:
            total_kb = degree * self.incast_kb_per_flow
        wp = WorkloadParams(workload=workload or self.workload, load=load,
                            incast_load=self.incast_load,
                            incast_degree=degree,
                            incast_total_kb=total_kb,
                            locality=self.locality, seed=seed)
        return generate(topo, wp, n_flows or self.n_flows,
                        long_lived=self.long_lived,
                        long_lived_pkts=(long_lived_pkts
                                         if long_lived_pkts is not None
                                         else self.long_lived_pkts))

    def cases(self, topo: Optional[Topology] = None,
              n_flows: Optional[int] = None,
              protos: Optional[Sequence[str]] = None,
              long_lived_pkts: Optional[int] = None,
              ) -> List[Tuple[str, SimConfig, "object"]]:
        """Expand to (label, SimConfig, FlowSet); flow sets are generated
        once per (topology, load, seed, degree) and shared across protocol
        variants. With a `topologies` axis, `topo` is ignored and each lane
        carries its own fabric in `SimConfig.clos`."""
        closes = self.topology_axis(topo.params if topo is not None
                                    else None)
        degs = self.degree_axis()
        wls = self.workload_axis()
        flowsets = {}
        for ci, clos in enumerate(closes):
            t = (topo if topo is not None and clos == topo.params
                 else build_cached(clos))
            for l in self.loads:
                for s in self.seeds:
                    for d in degs:
                        for w in wls:
                            flowsets[(ci, l, s, d, w)] = self.flowset(
                                t, l, s, n_flows, incast_degree=d,
                                long_lived_pkts=long_lived_pkts,
                                workload=w)
        out = []
        for p in (protos or self.protos):
            for (ci, l, s, d, w), fl in flowsets.items():
                cfg = SimConfig(proto=PRESETS[p], clos=closes[ci])
                label = f"{self.name}/{p}"
                if len(closes) > 1:
                    label += f"_{topo_tag(closes[ci])}"
                if len(wls) > 1:
                    label += f"_{w}"
                label += f"_load{int(l * 100)}"
                if len(degs) > 1:
                    label += f"_deg{d}"
                label += f"_seed{s}"
                out.append((label, cfg, fl))
        return out


SCENARIOS: Dict[str, Scenario] = {}


def register(sc: Scenario) -> Scenario:
    if sc.name in SCENARIOS:
        raise ValueError(f"duplicate scenario {sc.name!r}")
    SCENARIOS[sc.name] = sc
    return sc


def get(name: str) -> Scenario:
    try:
        return SCENARIOS[name]
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; have {names()}") from None


def names() -> List[str]:
    return sorted(SCENARIOS)


def run(name_or_scenario, clos: Optional[FabricParams] = None,
        n_flows: Optional[int] = None, drain: Optional[int] = None,
        unroll: int = 1, max_batch_bytes: Optional[int] = None,
        devices: Optional[Sequence] = None, auto_budget: bool = True,
        store=None, early_exit: bool = True, resume: bool = False,
        long_lived_pkts: Optional[int] = None, trace=None,
        n_ticks: Optional[int] = None):
    """Run one registry scenario through the batched sweep subsystem.

    `clos` sets the fabric (a leaf-spine's `ClosParams`, the default, or a
    `FatTreeParams`) for scenarios without their own `topologies` axis
    (scenarios WITH one pin their fabrics absolutely). Execution
    placement — chunk width, multi-device sharding, chunk spooling — is
    planned per protocol group by `sim.exec` (`devices`, `auto_budget`,
    `max_batch_bytes`, `store` pass through to its planner/dispatcher;
    `resume=True` with a store reuses the chunks an interrupted run of
    the same scenario already spooled — see `exec.resume`).
    `early_exit=False` forces the flat scan (A/B timing baseline);
    `long_lived_pkts` overrides the long-lived flow size (smoke-scale runs
    of `table1_long_lived` use it so the probe flow can complete and the
    drain tail goes quiescent). A `trace` TraceSpec turns on per-tick
    channel capture for every case of the grid (spooled per segment when
    a `store` is given; see sim/trace/). `n_ticks` cuts every lane's
    simulated horizon to that many ticks (default: the grid's largest
    flow horizon plus the drain). Returns a list of
    sweep.CaseResult (one per grid point), each carrying per-config
    SimState, emits, and summarized RunMetrics. Grids containing the
    centralized oracle get every lane's metrics annotated with
    `distance_from_optimal` (the p99 ratio vs the oracle case sharing
    its workload/fabric/load/seed)."""
    from . import metrics, sweep
    sc = (name_or_scenario if isinstance(name_or_scenario, Scenario)
          else get(name_or_scenario))
    topo = build(clos or ClosParams())
    cases = sc.cases(topo, n_flows=n_flows, long_lived_pkts=long_lived_pkts)
    if trace is not None:
        cases = [(label, replace(cfg, trace=trace), fl)
                 for label, cfg, fl in cases]
    results = sweep.run_grid(topo, cases, n_ticks=n_ticks,
                             drain=(drain if drain is not None
                                    else sc.drain_ticks),
                             unroll=unroll, max_batch_bytes=max_batch_bytes,
                             devices=devices, auto_budget=auto_budget,
                             store=store, early_exit=early_exit,
                             resume=resume)
    if any(r.proto == metrics.ORACLE_PROTO for r in results):
        metrics.distance_from_optimal(results)
    return results


# ---- the paper's grid --------------------------------------------------------
register(Scenario(
    name="fig5_load_sweep", paper_ref="Fig. 5 / Fig. 16",
    description="BFC vs DCTCP, Facebook-Hadoop sizes, 50-90% core load",
    workload="fb_hadoop", protos=("bfc", "dctcp"),
    loads=(0.5, 0.7, 0.8, 0.9), seeds=(16,)))

register(Scenario(
    name="fig6_incast", paper_ref="Fig. 6 / Fig. 9",
    description="Google workload + 5% incast cross traffic, all schemes",
    workload="google", protos=("bfc", "hpcc", "dcqcn", "dctcp", "ideal_fq"),
    loads=(0.55,), seeds=(9,), incast_load=0.05))

register(Scenario(
    name="fig10_noincast", paper_ref="Fig. 10",
    description="Google workload at 60% load, no incast, all schemes",
    workload="google", protos=("bfc", "hpcc", "dcqcn", "dctcp", "ideal_fq"),
    loads=(0.6,), seeds=(9,)))

register(Scenario(
    name="fig11_noincast", paper_ref="Fig. 11",
    description="Facebook-Hadoop sizes at 60% load, no incast",
    workload="fb_hadoop", protos=("bfc", "hpcc", "dctcp", "ideal_fq"),
    loads=(0.6,), seeds=(11,)))

register(Scenario(
    name="fig11_incast", paper_ref="Fig. 11",
    description="Facebook-Hadoop sizes + 5% incast cross traffic",
    workload="fb_hadoop", protos=("bfc", "hpcc", "dctcp", "ideal_fq"),
    loads=(0.55,), seeds=(11,), incast_load=0.05))

register(Scenario(
    name="table1_long_lived", paper_ref="Table 1 / Fig. 5",
    description="one long-lived flow vs variable cross traffic",
    workload="fb_hadoop", protos=("bfc", "hpcc", "dcqcn", "hpcc_sfq"),
    loads=(0.6,), seeds=(5,), long_lived=1, drain_ticks=60_000))

register(Scenario(
    name="websearch_tail",
    description="DCTCP WebSearch sizes: heavy tail at moderate/high load",
    workload="websearch", protos=("bfc", "hpcc", "dctcp"),
    loads=(0.6, 0.8), seeds=(2, 3)))

register(Scenario(
    name="fig17_incast_degree", paper_ref="Fig. 17",
    description="incast degree sweep 4-64 (Fig. 17): flow- vs dest-keyed "
                "BFC queues vs HPCC as fan-in exhausts physical queues",
    workload="fb_hadoop", protos=("bfc", "bfc_dest", "hpcc"),
    loads=(0.55,), seeds=(17,), incast_load=0.05,
    incast_degrees=(4, 8, 16, 32, 64), incast_kb_per_flow=200))

# ---- beyond the paper --------------------------------------------------------
register(Scenario(
    name="rack_local_skew",
    description="70% rack-local traffic: backpressure must not hurt "
                "intra-rack flows when the core is quiet",
    workload="fb_hadoop", protos=("bfc", "dctcp"),
    loads=(0.6, 0.8), seeds=(4,), locality=0.7))

register(Scenario(
    name="incast_plus_background",
    description="10% incast over a loaded fabric; queue-exhaustion regime "
                "for flow- vs dest-keyed BFC queues",
    workload="google", protos=("bfc", "bfc_dest", "hpcc"),
    loads=(0.5, 0.7), seeds=(6,), incast_load=0.10, incast_degree=40,
    incast_total_kb=8000))

register(Scenario(
    name="oversub_sweep",
    description="core oversubscription 4:1 / 2:1 / 1:1 (spine count axis): "
                "per-hop backpressure vs e2e CC as the core thins; the "
                "three fabrics ride one compiled program's batch axis",
    workload="fb_hadoop", protos=("bfc", "dctcp"),
    loads=(0.6,), seeds=(7,),
    topologies=(ClosParams(n_servers=64, n_tor=8, n_spine=2,
                           switch_buffer_pkts=8192),
                ClosParams(n_servers=64, n_tor=8, n_spine=4,
                           switch_buffer_pkts=8192),
                ClosParams(n_servers=64, n_tor=8, n_spine=8,
                           switch_buffer_pkts=8192))))

def _latency_fabric(prop: int, buffer_pkts: int = 8192) -> ClosParams:
    """A half-scale fabric whose only varying knob is the link delay."""
    return ClosParams(n_servers=64, n_tor=8, n_spine=8, prop_ticks=prop,
                      switch_buffer_pkts=buffer_pkts)


register(Scenario(
    name="rtt_sweep",
    description="link propagation 1-64 ticks (sub-us rack to campus "
                "scale): how sensitive is each scheme to wire delay the "
                "protocol was NOT retuned for? Timing constants (RTT "
                "epochs, pause window, initial windows) stay at the "
                "paper's prop=12 calibration by design — retuning them "
                "per delay would split the compile group (timing is "
                "static) and would measure configuration, not protocol. "
                "Every delay rides the batch axis of one compilation "
                "per protocol (prop_ticks is a traced operand)",
    workload="fb_hadoop", protos=("bfc", "dctcp", "hpcc"),
    loads=(0.6,), seeds=(21,),
    topologies=tuple(_latency_fabric(p) for p in (1, 4, 12, 32, 64))))

register(Scenario(
    name="cross_dc_latency",
    description="long-haul link delays (12 / 32 / 64 ticks) under 60% "
                "rack-local cross traffic: pause propagation must not "
                "penalize rack-local flows as the wires between racks "
                "get slow; mixed-latency lanes batch into one program "
                "(timing constants deliberately frozen at the prop=12 "
                "calibration — see rtt_sweep)",
    workload="fb_hadoop", protos=("bfc", "dctcp"),
    loads=(0.6,), seeds=(22,), locality=0.6,
    topologies=tuple(_latency_fabric(p) for p in (12, 32, 64))))

register(Scenario(
    name="protocol_zoo",
    description="every protocol family head-to-head -- BFC (+SRF), PFC, "
                "DCTCP, DCQCN, HPCC (+SFQ), Ideal-FQ, and the post-BFC "
                "literature: SFC near-source pausing, FairQ fair-rate "
                "allocation, and the centralized SRPT oracle -- across "
                "the paper's three workload families; the oracle lane "
                "gives every case a distance_from_optimal column (one "
                "compilation per family, workloads ride the batch axis)",
    workload="google", workloads=("google", "fb_hadoop", "websearch"),
    protos=("bfc", "bfc_srf", "pfc", "dctcp", "dcqcn", "hpcc", "hpcc_sfq",
            "sfc", "fairq", "ideal_fq", "oracle"),
    loads=(0.6,), seeds=(42,)))

register(Scenario(
    name="buffer_sweep",
    description="switch buffer 2MB -> 12MB: BFC's advantage concentrates "
                "in shallow-buffer fabrics (buffer_limit is a traced "
                "operand, so all sizes share one compilation)",
    workload="fb_hadoop", protos=("bfc", "dctcp", "hpcc"),
    loads=(0.6,), seeds=(13,), incast_load=0.05,
    topologies=(ClosParams(n_servers=64, n_tor=8, n_spine=8,
                           switch_buffer_pkts=2048),
                ClosParams(n_servers=64, n_tor=8, n_spine=8,
                           switch_buffer_pkts=4096),
                ClosParams(n_servers=64, n_tor=8, n_spine=8,
                           switch_buffer_pkts=12288))))
