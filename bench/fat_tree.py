"""The configuration module of a k-ary fat-tree (Al-Fares, Loukissas and
Vahdat, "A Scalable, Commodity Data Center Network Architecture", SIGCOMM
2008, section 3). See `leaf_spine.py` for what a module defines.

Its port graph is written here from the layout alone, and imports nothing
of the program (only `program()` does). With h = k/2:

* NIC ports are [0, k^3/4), one per host.
* Switch ids: the k*h edges first, then the k*h aggregations, then the h*h
  cores. Pod p holds edges h*p .. h*p + h - 1 and the aggregations at the
  same offsets.
* Every switch has k ports, numbered after the NICs switch by switch: an
  edge's h down-ports (its hosts, in host order), then its h up-ports (its
  pod's aggregations, in order); an aggregation's h down-ports (its pod's
  edges), then its h up-ports, up-port i of the pod's aggregation j going
  to core h*j + i; a core's k down-ports, one per pod.
* Routes: same edge [NIC, edge down]; same pod [NIC, edge up j, agg down,
  edge down]; another pod [NIC, edge up j, agg up i, core h*j+i down to
  the destination pod, agg down, edge down]; -1 padded to 6 hops. j and i
  are two independent 32-bit hashes of the flow id (seeds 5 and 7), mod h.

Flows are `flowgen`'s sampling, its load normalised over the k^3/4
aggregation-to-core links (pods x cores, as the leaf-spine's are ToRs x
spines), carrying the share 1 - 1/k of flows that leave their pod. The
reference is the shared `reference.py`, at the routes' width.
"""
from dataclasses import dataclass

import numpy as np

import flowgen
import reference

HOPS = 6
ECMP_SEEDS = (5, 7)


@dataclass(frozen=True)
class FatTree:
    k: int
    prop_ticks: int
    switch_buffer_pkts: int

    @property
    def h(self) -> int:
        return self.k // 2

    @property
    def n_servers(self) -> int:
        return self.k ** 3 // 4

    @property
    def n_edge(self) -> int:
        return self.k * self.h

    @property
    def n_core(self) -> int:
        return self.h * self.h

    @property
    def n_switches(self) -> int:
        return 2 * self.n_edge + self.n_core

    @property
    def n_ports(self) -> int:
        return self.n_servers + self.n_switches * self.k

    def edge(self, pod, j):
        return pod * self.h + j

    def agg(self, pod, j):
        return self.n_edge + pod * self.h + j

    def core(self, j, i):
        return 2 * self.n_edge + j * self.h + i

    def port(self, switch, local):
        return self.n_servers + switch * self.k + local

    def port_switch(self) -> np.ndarray:
        """Owning switch of each port; -1 = NIC."""
        out = np.full(self.n_ports, -1, np.int64)
        for sw in range(self.n_switches):
            out[self.port(sw, 0):self.port(sw, self.k)] = sw
        return out

    def feeds(self) -> np.ndarray:
        """Switch whose buffer a packet sent from each port enters (-1: a
        server)."""
        h = self.h
        out = np.full(self.n_ports, -1, np.int64)
        for host in range(self.n_servers):
            out[host] = host // h
        for pod in range(self.k):
            for j in range(h):
                for x in range(h):
                    out[self.port(self.edge(pod, j), h + x)] = \
                        self.agg(pod, x)
                    out[self.port(self.agg(pod, j), x)] = self.edge(pod, x)
                    out[self.port(self.agg(pod, j), h + x)] = \
                        self.core(j, x)
        for j in range(h):
            for i in range(h):
                for pod in range(self.k):
                    out[self.port(self.core(j, i), pod)] = self.agg(pod, j)
        return out

    def routes(self, src, dst, fid) -> np.ndarray:
        """Egress ports each flow is sent from, hop by hop, -1 padded."""
        h = self.h
        j = (flowgen.hash_u32(fid, ECMP_SEEDS[0]) % np.uint32(h)).astype(
            np.int64)
        i = (flowgen.hash_u32(fid, ECMP_SEEDS[1]) % np.uint32(h)).astype(
            np.int64)
        r = np.full((len(src), HOPS), -1, np.int64)
        for f, (s, d) in enumerate(zip(np.asarray(src), np.asarray(dst))):
            s_edge, d_edge = s // h, d // h
            s_pod, d_pod = s_edge // h, d_edge // h
            down = self.port(d_edge, d % h)
            if s_edge == d_edge:
                path = [s, down]
            elif s_pod == d_pod:
                path = [s, self.port(s_edge, h + j[f]),
                        self.port(self.agg(s_pod, j[f]), d_edge % h), down]
            else:
                path = [s, self.port(s_edge, h + j[f]),
                        self.port(self.agg(s_pod, j[f]), h + i[f]),
                        self.port(self.core(j[f], i[f]), d_pod),
                        self.port(self.agg(d_pod, j[f]), d_edge % h), down]
            r[f, :len(path)] = path
        return r.astype(np.int32)


@dataclass(frozen=True)
class _CoreView:
    """The fabric as `flowgen.generate` reads it: load normalised over
    n_tor * n_spine core links carrying the share 1 - 1/n_tor of flows.
    For the fat-tree those are its k pods and (k/2)^2 cores, whose product
    is the k^3/4 aggregation-to-core links; routes are the tree's own."""
    tree: FatTree

    @property
    def n_servers(self) -> int:
        return self.tree.n_servers

    @property
    def n_tor(self) -> int:
        return self.tree.k

    @property
    def n_spine(self) -> int:
        return self.tree.n_core

    @property
    def per_tor(self) -> int:
        return self.tree.n_servers // self.tree.k

    @property
    def prop_ticks(self) -> int:
        return self.tree.prop_ticks

    def routes(self, src, dst, fid) -> np.ndarray:
        return self.tree.routes(src, dst, fid)


def fabric(config: dict) -> FatTree:
    return FatTree(**config["fabric"])


def generate(fat_tree: FatTree, traffic: dict, seed: int) -> dict:
    """One lane's flows: `flowgen.generate` on the tree's core view, with
    (F, 6) routes."""
    return flowgen.generate(_CoreView(fat_tree), traffic, seed)


simulate = reference.simulate
summarize = reference.summarize


def program(config: dict):
    """The program's SimConfig and fat-tree from a configuration file."""
    from repro.sim.config import ProtoConfig, SimConfig, TimingParams
    from repro.sim.topology import FatTreeParams, build
    cfg = SimConfig(proto=ProtoConfig(**config["proto"]),
                    timing=TimingParams(**config["timing"]),
                    clos=FatTreeParams(**config["fabric"]), **config["sim"])
    return cfg, build(cfg.clos)
