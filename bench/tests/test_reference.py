"""The reference agrees with the program exactly at a small size (CPU)."""
import numpy as np
import pytest

import check
import flowgen
import harness
import reference
from conftest import small_config, small_traffic


def run_program(config, traffic, seed, n_ticks):
    from repro.sim import sweep
    topo, cases, flows = harness.build_cases(config, traffic, seed)
    res = sweep.run_grid(topo, cases, n_ticks=n_ticks,
                         pad_multiple=traffic["flows_padded_to"])
    return res, flows


@pytest.mark.parametrize("config_name", ["bfc_paper", "dcqcn_paper"])
def test_program_equals_reference(config_name):
    config = small_config(config_name)
    traffic = small_traffic("fig6_x1")
    res, flows = run_program(config, traffic, 11, traffic["n_ticks"])
    rules = harness.float_rules("cpu")
    mm, gap, det = harness.judge(res, flows, [0], config, rules)
    assert (mm, gap, det) == (0, 0.0, {})
    m = res[0].metrics
    # the case exercises what the cell's why names
    if config_name == "bfc_paper":
        assert m.pauses > 0 and m.collisions >= 0
    else:
        assert m.drops > 0 or m.pfc_pause_frac > 0


def test_bfloat16_control_fails():
    config = small_config("dcqcn_paper")
    traffic = small_traffic("fig6_x1")
    fabric = flowgen.fabric_of(config)
    flows = flowgen.generate(fabric, traffic, 11)
    rules = harness.float_rules("cpu")
    import ml_dtypes
    st32, em32 = reference.simulate(fabric, config, flows, 300, rules)
    st16, em16 = reference.simulate(fabric, config, flows, 300, rules,
                                    ml_dtypes.bfloat16)
    m32 = reference.summarize(st32, em32, flows, fabric.n_ports)
    m16 = reference.summarize(st16, em16, flows, fabric.n_ports)
    import types
    state = types.SimpleNamespace(**st16)
    state._fields = tuple(st16)
    mm, gap, _ = check.compare_lane(state, em16, types.SimpleNamespace(**m16),
                                    st32, em32, m32)
    assert gap > harness.FLOAT_GAP_LIMIT


def test_reference_refuses_what_it_does_not_model():
    config = small_config("bfc_paper")
    config["proto"]["scheduler"] = "srf"
    fabric = flowgen.fabric_of(config)
    flows = flowgen.generate(fabric, small_traffic("fig6_x1"), 1)
    with pytest.raises(NotImplementedError):
        reference.Reference(fabric, config, flows, {})


def test_hash_matches_program():
    import jax.numpy as jnp
    from repro.core.hashing import hash_u32
    x = np.arange(-50, 5000, 7, dtype=np.int32)
    for seed in range(6):
        want = np.asarray(hash_u32(jnp.asarray(x), seed))
        assert np.array_equal(flowgen.hash_u32(x, seed), want)


HOP_TABLES = ("f_q", "f_cnt", "f_paused")
RINGS = ("ack_ring", "mark_ring", "u_ring", "sfc_ring")


@pytest.mark.parametrize("config_name", ["bfc_paper", "dcqcn_paper"])
def test_hop_width_follows_the_routes(config_name):
    """The same flows with `routes` padded from 4 to 6 columns with -1 give
    the same final state and emits: the hop tables gain two columns and
    the feedback rings 2 * prop_ticks rows, all of them left untouched."""
    config = small_config(config_name)
    fabric = flowgen.fabric_of(config)
    flows = flowgen.generate(fabric, small_traffic("fig6_x1"), 23)
    wide = dict(flows, routes=np.pad(flows["routes"], ((0, 0), (0, 2)),
                                     constant_values=-1))
    rules = harness.float_rules("cpu")
    st4, em4 = reference.simulate(fabric, config, flows, 300, rules)
    st6, em6 = reference.simulate(fabric, config, wide, 300, rules)
    assert np.array_equal(em4, em6)
    # the hop tables and the rings are in use at the end
    assert st4["f_cnt"][:, 3].any() and st4["ack_ring"].any()
    rows = 4 * fabric.prop_ticks + 2
    for name, got in st6.items():
        want = st4[name]
        if name in HOP_TABLES:
            assert got.shape == (len(flows["src"]), 6), name
            assert (got[:, 4:] == got.dtype.type(name == "f_q" and -1)).all()
            got = got[:, :4]
        elif name in RINGS:
            assert got.shape[0] == 6 * fabric.prop_ticks + 2, name
            assert not got[rows:].any(), name
            got = got[:rows]
        assert np.array_equal(got, want), name


def test_limits_control_fails_through_the_module():
    """`limits.control_readings` runs the configuration's module's
    reference in bfloat16 over every lane, and the control fails."""
    import limits
    config = small_config("dcqcn_paper")
    traffic = small_traffic("fig6_x1", lanes=2, n_ticks=300)
    mm, gap = limits.control_readings(config, traffic, 11,
                                      harness.float_rules("cpu"), [0, 1])
    assert gap > harness.FLOAT_GAP_LIMIT
