"""The default configuration module: the two-tier leaf-spine.

A configuration file names the module that builds its fabric, flows,
program case and reference under the key `module` (a path relative to
`bench/`); one without the key gets this one. A module defines:

* `fabric(config)`: the port graph the generator and the reference read
  (`n_ports`, `n_servers`, `n_switches`, `prop_ticks`,
  `switch_buffer_pkts`, `port_switch()`, `feeds()`);
* `program(config)`: the program's `(SimConfig, Topology)`, built through
  its own public modules;
* `generate(fabric, traffic, seed)`: one lane's flows, a dict of
  `flowgen.ARRAYS` and `horizon`; `routes` may be of any width;
* `simulate(fabric, config, flows, n_ticks, rules, fdtype)` and
  `summarize(state, emits, flows, n_ports)`: the reference.
"""
from __future__ import annotations

import flowgen
import reference

fabric = flowgen.fabric_of
generate = flowgen.generate
simulate = reference.simulate
summarize = reference.summarize


def program(config: dict):
    """The program's SimConfig and leaf-spine from a configuration file."""
    from repro.sim.config import ProtoConfig, SimConfig, TimingParams
    from repro.sim.topology import ClosParams, build
    cfg = SimConfig(proto=ProtoConfig(**config["proto"]),
                    timing=TimingParams(**config["timing"]),
                    clos=ClosParams(**config["fabric"]), **config["sim"])
    return cfg, build(cfg.clos)
