"""The benchmark's traffic generator is pinned, and equals the program's."""
import hashlib

import numpy as np
import pytest

import flowgen
from conftest import small_config, small_traffic

PINNED = {
    1: (460, 8553, {
        "src": "0d372ecbde992d8e", "dst": "ac0c98a2576f17bd",
        "size_pkts": "81097b0f99489463", "arrival_tick": "b36fc9810f05dfd9",
        "routes": "7fdebcb600c45471", "ideal_fct": "5aa4897c36355991",
        "fid": "c9ddf0194b084383", "is_incast": "83fc432f77645ded"}),
    2**31 + 7: (440, 5069, {
        "src": "77ee4fb3a798a39d", "dst": "23ed1c9609c1f42d",
        "size_pkts": "94eaaed339e34851", "arrival_tick": "596c520e85cd1465",
        "routes": "0c401a319bbf9235", "ideal_fct": "fd8cce7d3f7ada32",
        "fid": "0218eaabbf9007c0", "is_incast": "7c51d43e615cfa47"}),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_checksums(seed):
    fabric = flowgen.fabric_of(small_config("bfc_paper"))
    flows = flowgen.generate(fabric, small_traffic("fig6_x1"), seed)
    n, horizon, sums = PINNED[seed]
    assert len(flows["src"]) == n and flows["horizon"] == horizon
    got = {k: hashlib.sha256(flows[k].tobytes()).hexdigest()[:16]
           for k in flowgen.ARRAYS}
    assert got == sums


@pytest.mark.parametrize("seed", [9, 2**31 + 12345])
def test_equals_program_generator_at_paper_scale(seed):
    import json
    from repro.sim.topology import ClosParams, build
    from repro.sim.workload import WorkloadParams, generate
    from conftest import BENCH
    config = json.loads((BENCH / "configs" / "bfc_paper.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "fig6_x1.json").read_text())
    fabric = flowgen.fabric_of(config)
    ours = flowgen.generate(fabric, traffic, seed)
    topo = build(ClosParams(**config["fabric"]))
    theirs = generate(topo, WorkloadParams(
        workload=traffic["workload"], load=traffic["load"],
        incast_load=traffic["incast_load"],
        incast_degree=traffic["incast_degree"],
        incast_total_kb=traffic["incast_total_kb"], seed=seed),
        traffic["background_flows"])
    assert (fabric.port_switch() == topo.port_switch).all()
    for key in flowgen.ARRAYS:
        assert np.array_equal(ours[key], getattr(theirs, key)), key
    assert ours["horizon"] == theirs.horizon
    if seed == 9:
        assert len(ours["src"]) == 4500


def test_every_seed_fits_the_padding():
    """Flow counts of the Fig. 6 mix stay under the padded count."""
    import json
    from conftest import BENCH
    config = json.loads((BENCH / "configs" / "bfc_paper.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "fig6_x1.json").read_text())
    fabric = flowgen.fabric_of(config)
    counts = [len(flowgen.generate(fabric, traffic, s)["src"])
              for s in range(40)]
    assert max(counts) <= traffic["flows_padded_to"]
