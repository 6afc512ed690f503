"""The benchmark's own traffic generator and fabric tables (numpy only).

A copy of the simulator's flow synthesis (paper section 4.1, Fig. 2) kept
with the benchmark, so that no change to the program can move the
yardstick: flow sizes from piecewise log-linear CDFs, lognormal
inter-arrivals (sigma 2) scaled to the offered core load, uniform
source/destination pairs, and Poisson N-to-1 incast events. Routing
follows the leaf-spine port numbering the simulator uses (server NIC
uplinks, then per ToR its down-ports and up-ports, then spine
down-ports) with flow-level ECMP by a 32-bit hash of the flow id.

It imports nothing of the program. `tests/test_flowgen.py` pins its arrays
by checksum, and PERF.md records that it equals the program's
`workload.generate` at paper scale for seed 9.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# the leaf-spine's route width (NIC, ToR, spine, ToR); the reference takes
# its hop width from the routes it is given
MAX_HOPS = 4

# (size_in_KB, CDF by count) control points, log-linear interpolation.
SIZE_CDFS = {
    "google": [(1, 0.35), (2, 0.45), (4, 0.55), (8, 0.62), (16, 0.70),
               (32, 0.77), (64, 0.83), (128, 0.88), (256, 0.92), (512, 0.95),
               (1024, 0.97), (4096, 0.988), (16384, 0.996), (65536, 1.0)],
    "fb_hadoop": [(1, 0.50), (2, 0.62), (4, 0.70), (8, 0.75), (16, 0.79),
                  (32, 0.83), (64, 0.87), (128, 0.91), (256, 0.94),
                  (512, 0.96), (1024, 0.975), (2048, 0.985), (4096, 0.992),
                  (10240, 1.0)],
    "websearch": [(1, 0.15), (4, 0.30), (16, 0.45), (64, 0.60), (256, 0.75),
                  (1024, 0.87), (4096, 0.95), (10240, 0.98), (30720, 1.0)],
    "uniform": [(1, 0.0), (64, 1.0)],
}

_MULTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1,
          0x61C88647)


def hash_u32(x, seed: int) -> np.ndarray:
    """Seeded 32-bit avalanche hash of an integer array (wrapping uint32)."""
    x = np.asarray(x).astype(np.uint32) * np.uint32(_MULTS[seed % 6])
    x = x + np.uint32((seed * 0x01000193 + 0x811C9DC5) & 0xFFFFFFFF)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


@dataclass(frozen=True)
class Fabric:
    """A two-tier leaf-spine fabric and its port numbering."""
    n_servers: int
    n_tor: int
    n_spine: int
    prop_ticks: int
    switch_buffer_pkts: int

    @property
    def per_tor(self) -> int:
        return self.n_servers // self.n_tor

    @property
    def ports_per_tor(self) -> int:
        return self.per_tor + self.n_spine

    @property
    def tor_base(self) -> int:
        return self.n_servers

    @property
    def spine_base(self) -> int:
        return self.n_servers + self.n_tor * self.ports_per_tor

    @property
    def n_ports(self) -> int:
        return self.spine_base + self.n_spine * self.n_tor

    @property
    def n_switches(self) -> int:
        return self.n_tor + self.n_spine

    def tor_down(self, tor, server):
        return self.tor_base + tor * self.ports_per_tor + server % self.per_tor

    def tor_up(self, tor, spine):
        return self.tor_base + tor * self.ports_per_tor + self.per_tor + spine

    def spine_down(self, spine, tor):
        return self.spine_base + spine * self.n_tor + tor

    def port_switch(self) -> np.ndarray:
        """Owning switch of each port (ToRs first, then spines); -1 = NIC."""
        out = np.full(self.n_ports, -1, np.int64)
        for tor in range(self.n_tor):
            lo = self.tor_base + tor * self.ports_per_tor
            out[lo:lo + self.ports_per_tor] = tor
        for sp in range(self.n_spine):
            lo = self.spine_base + sp * self.n_tor
            out[lo:lo + self.n_tor] = self.n_tor + sp
        return out

    def feeds(self) -> np.ndarray:
        """Switch whose buffer a packet sent from each port enters (-1: a
        server)."""
        out = np.full(self.n_ports, -1, np.int64)
        out[:self.n_servers] = np.arange(self.n_servers) // self.per_tor
        for tor in range(self.n_tor):
            for sp in range(self.n_spine):
                out[self.tor_up(tor, sp)] = self.n_tor + sp
                out[self.spine_down(sp, tor)] = tor
        return out

    def routes(self, src, dst, fid) -> np.ndarray:
        """Egress ports each flow is sent from, hop by hop, -1 padded."""
        src, dst = np.asarray(src), np.asarray(dst)
        spine = hash_u32(fid, 5) % np.uint32(self.n_spine)
        spine = spine.astype(np.int64)
        s_tor, d_tor = src // self.per_tor, dst // self.per_tor
        r = np.full((len(src), MAX_HOPS), -1, np.int64)
        r[:, 0] = src
        intra = s_tor == d_tor
        inter = ~intra
        r[intra, 1] = self.tor_down(d_tor[intra], dst[intra])
        r[inter, 1] = self.tor_up(s_tor[inter], spine[inter])
        r[inter, 2] = self.spine_down(spine[inter], d_tor[inter])
        r[inter, 3] = self.tor_down(d_tor[inter], dst[inter])
        return r.astype(np.int32)


def fabric_of(config: dict) -> Fabric:
    f = config["fabric"]
    return Fabric(n_servers=f["n_servers"], n_tor=f["n_tor"],
                  n_spine=f["n_spine"], prop_ticks=f["prop_ticks"],
                  switch_buffer_pkts=f["switch_buffer_pkts"])


def sample_sizes(rng, n: int, workload: str, mtu_kb: int = 1) -> np.ndarray:
    pts = SIZE_CDFS[workload]
    sizes_kb = np.array([p[0] for p in pts], float)
    cdf = np.array([p[1] for p in pts], float)
    u = rng.random(n)
    logs = np.interp(u, np.concatenate([[0.0], cdf]),
                     np.concatenate([[np.log(sizes_kb[0])],
                                     np.log(sizes_kb)]))
    return np.maximum(1, np.round(np.exp(logs) / mtu_kb)).astype(np.int32)


def generate(fabric: Fabric, traffic: dict, seed: int) -> dict:
    """One lane's flows under a traffic mix, from `seed`.

    Returns a dict of arrays sorted by arrival tick: src, dst, size_pkts,
    arrival_tick, routes (F, 4), ideal_fct, fid, is_incast, and the
    horizon (last background arrival tick)."""
    rng = np.random.default_rng(seed)
    n = traffic["background_flows"]
    mtu_kb = traffic.get("mtu_kb", 1)
    locality = traffic.get("locality", 0.0)
    sizes = sample_sizes(rng, n, traffic["workload"], mtu_kb)

    inter_frac = (1.0 - locality) * (1.0 - 1.0 / fabric.n_tor)
    core_links = fabric.n_tor * fabric.n_spine
    mean_size = float(sizes.mean())
    lam = traffic["load"] * core_links / (mean_size * max(inter_frac, 1e-6))
    sig = traffic.get("sigma", 2.0)
    mu_ln = np.log(1.0 / lam) - 0.5 * sig * sig
    arrivals = np.floor(np.cumsum(
        rng.lognormal(mean=mu_ln, sigma=sig, size=n))).astype(np.int64)

    src = rng.integers(0, fabric.n_servers, n)
    dst = rng.integers(0, fabric.n_servers, n)
    same = dst == src
    dst[same] = (dst[same] + 1
                 + rng.integers(0, fabric.n_servers - 1, same.sum())) \
        % fabric.n_servers
    if locality > 0:
        local = rng.random(n) < locality
        rack = src // fabric.per_tor
        off = rng.integers(1, fabric.per_tor, local.sum())
        dst[local] = rack[local] * fabric.per_tor + \
            (src[local] % fabric.per_tor + off) % fabric.per_tor

    is_incast = np.zeros(n, bool)
    horizon = int(arrivals.max()) if n else 0
    incast_load = traffic.get("incast_load", 0.0)
    if incast_load > 0:
        degree = traffic["incast_degree"]
        per_flow_kb = max(1, traffic["incast_total_kb"] // degree)
        per_event = degree * (per_flow_kb // mtu_kb)
        ev_rate = incast_load * core_links / max(per_event, 1)
        n_events = max(1, int(np.floor(horizon * ev_rate)))
        ev_ticks = np.sort(rng.integers(0, max(horizon, 1), n_events))
        i_src, i_dst, i_arr = [], [], []
        for t in ev_ticks:
            victim = int(rng.integers(0, fabric.n_servers))
            senders = rng.choice(
                np.setdiff1d(np.arange(fabric.n_servers), [victim]),
                size=min(degree, fabric.n_servers - 1), replace=False)
            i_src.append(senders)
            i_dst.append(np.full(len(senders), victim))
            i_arr.append(np.full(len(senders), t))
        i_src = np.concatenate(i_src)
        src = np.concatenate([src, i_src])
        dst = np.concatenate([dst, np.concatenate(i_dst)])
        sizes = np.concatenate(
            [sizes, np.full(len(i_src), per_flow_kb // mtu_kb, np.int32)])
        arrivals = np.concatenate([arrivals, np.concatenate(i_arr)])
        is_incast = np.concatenate([is_incast, np.ones(len(i_src), bool)])

    order = np.argsort(arrivals, kind="stable")
    src, dst = src[order], dst[order]
    sizes, arrivals, is_incast = sizes[order], arrivals[order], \
        is_incast[order]
    fid = ((np.arange(len(src), dtype=np.int64) * 2654435761
            + seed * 97 + 1) % (1 << 31)).astype(np.int32)
    routes = fabric.routes(src, dst, fid)
    hops = (routes >= 0).sum(axis=1)
    ideal = sizes.astype(np.int64) + hops * fabric.prop_ticks
    return dict(src=src.astype(np.int32), dst=dst.astype(np.int32),
                size_pkts=sizes.astype(np.int32),
                arrival_tick=arrivals.astype(np.int32), routes=routes,
                ideal_fct=ideal.astype(np.int32), fid=fid,
                is_incast=is_incast, horizon=horizon)


ARRAYS = ("src", "dst", "size_pkts", "arrival_tick", "routes", "ideal_fct",
          "fid", "is_incast")
