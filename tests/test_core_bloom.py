"""Bloom filter unit + property tests (paper §3.3.2, Fig. 8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tier1
pytest.importorskip("hypothesis")  # optional dep: skip, don't break collection
from hypothesis import given, settings, strategies as st

from repro.core import bloom

BP = bloom.BloomParams()


def _pos(fids):
    return bloom.positions(jnp.asarray(np.asarray(fids, np.int32)), BP)


def test_sizes():
    assert BP.size_bytes == 128  # paper's 128 B pause frame


def test_insert_then_check():
    counts = bloom.empty_counts(BP)
    p = _pos([42])[0]
    counts = bloom.add(counts, p, True)
    assert bool(bloom.check(bloom.snapshot(counts), p))


def test_remove_clears():
    counts = bloom.empty_counts(BP)
    p = _pos([42])[0]
    counts = bloom.add(counts, p, True)
    counts = bloom.remove(counts, p, True)
    assert not bool(bloom.check(bloom.snapshot(counts), p))
    assert int(jnp.sum(counts)) == 0


def test_counting_protects_shared_bits():
    """Fig. 8: removing one flow must not clear another's bits."""
    counts = bloom.empty_counts(BP)
    pos = _pos([1, 2, 3, 4])
    for i in range(4):
        counts = bloom.add(counts, pos[i], True)
    counts = bloom.remove(counts, pos[0], True)
    snap = bloom.snapshot(counts)
    for i in range(1, 4):
        assert bool(bloom.check(snap, pos[i])), f"flow {i} lost its bits"


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=64,
                unique=True))
def test_no_false_negatives(fids):
    counts = bloom.empty_counts(BP)
    pos = _pos(fids)
    counts = bloom.add_batch(counts[None], jnp.zeros(len(fids), jnp.int32),
                             pos, jnp.ones(len(fids), jnp.int32))[0]
    snap = bloom.snapshot(counts)
    got = bloom.check(snap[None].repeat(len(fids), 0), pos)
    assert bool(jnp.all(got))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=2, max_size=32,
                unique=True),
       st.data())
def test_add_remove_batch_roundtrip(fids, data):
    """Inserting then removing any subset restores exactly the complement."""
    n = len(fids)
    counts = bloom.empty_counts(BP)
    pos = _pos(fids)
    zeros = jnp.zeros(n, jnp.int32)
    counts = bloom.add_batch(counts[None], zeros, pos,
                             jnp.ones(n, jnp.int32))[0]
    k = data.draw(st.integers(1, n - 1))
    counts = bloom.add_batch(counts[None], zeros[:k], pos[:k],
                             -jnp.ones(k, jnp.int32))[0]
    snap = bloom.snapshot(counts)
    kept = bloom.check(snap[None].repeat(n - k, 0), pos[k:])
    assert bool(jnp.all(kept))
    assert int(counts.sum()) == (n - k) * BP.n_stages


def test_false_positive_rate_small():
    """Paper: ~32 paused flows in 4x256 bits -> fp rate ~(1/8)^4."""
    rng = np.random.default_rng(0)
    members = rng.integers(0, 2**31, 32)
    counts = bloom.empty_counts(BP)
    pos = _pos(members)
    counts = bloom.add_batch(counts[None], jnp.zeros(32, jnp.int32), pos,
                             jnp.ones(32, jnp.int32))[0]
    snap = bloom.snapshot(counts)
    probes = rng.integers(0, 2**31, 20000)
    probes = np.setdiff1d(probes, members)
    got = bloom.check(snap[None].repeat(len(probes), 0), _pos(probes))
    fp = float(jnp.mean(got.astype(jnp.float32)))
    assert fp < 5e-3, fp


def _gather_check(bits, pos):
    """Element-gather reference: bit pos[..., s] of stage s, in every stage."""
    bits, pos = np.broadcast_arrays(np.asarray(bits),
                                    np.asarray(pos)[..., None])
    got = np.take_along_axis(bits, pos[..., :1], axis=-1)[..., 0]
    return got.all(axis=-1)


@pytest.mark.parametrize("lanes", [None, 3], ids=["one", "vmap"])
@pytest.mark.parametrize("n_stages", [1, 4])
@pytest.mark.parametrize("stage_bits", [64, 100, 256, 1024])
def test_check_equals_element_gather(stage_bits, n_stages, lanes):
    """Queue heads (P, Q, S) against their port's snapshot row broadcast as
    (P, 1, S, B): the dense test equals an element gather, first and last
    bit of a stage included, alone and vmapped over a lane axis."""
    P, Q = 6, 5
    lead = (lanes,) if lanes else ()
    rng = np.random.default_rng(stage_bits * 10 + n_stages)
    bits = rng.random(lead + (P, n_stages, stage_bits)) < 0.7
    pos = rng.integers(0, stage_bits, lead + (P, Q, n_stages), dtype=np.int32)
    pos[..., 0, :] = 0                    # every port's queue 0 reads bit 0
    pos[..., 1, :] = stage_bits - 1       # ... and queue 1 the last bit
    bits[..., 0, :, 0] = True             # set on port 0 in every stage
    bits[..., 1, :, stage_bits - 1] = False   # clear on port 1
    check = lambda b, p: bloom.check(b[:, None], p)   # noqa: E731
    if lanes:
        check = jax.vmap(check)
    got = np.asarray(check(jnp.asarray(bits), jnp.asarray(pos)))
    want = _gather_check(bits[..., :, None, :, :], pos)
    assert got.shape == lead + (P, Q)
    assert np.array_equal(got, want)
    assert want[..., 0, 0].all() and not want[..., 1, 1].any()
    assert want.sum() > want[..., 0, 0].size   # hits beyond the planted one
