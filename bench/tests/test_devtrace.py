"""The trace reduction: busy as a union of leaf intervals, the idle share,
and the switch-decision kernel's time found by name."""
import gzip
import json
import re
from pathlib import Path

import numpy as np
import pytest

import devtrace
import switch_decision
from devtrace import Event, Recording

KERNEL = re.compile("kern")


def hand_built():
    # a while loop (container) holding three ops, one of them the kernel;
    # ops of 10+20+5 ns with a 5 ns and a 60 us gap; a second device
    dev0 = [Event(0, 100_000, "%while.1 = (s32[]) while(...)"),
            Event(10, 20, "%fusion.1 = s32[8]{0} fusion(...)"),
            Event(15, 25, "%fusion.2 = s32[8]{0} fusion(...)"),   # overlaps
            Event(30, 35, "%custom-call.3 = s32[8]{0} kern(...)"),
            Event(60_035, 60_045, "%fusion.1 = s32[8]{0} fusion(...)")]
    dev1 = [Event(0, 50, "%fusion.9 = f32[2]{0} fusion(...)")]
    host = [Event(0, 100_000, devtrace.WINDOW_SPAN),
            Event(100, 60_000, "$dispatch.py:145 _land")]
    return Recording(devices={0: dev0, 1: dev1}, host=host)


def test_hand_built():
    red = devtrace.reduce(hand_built(), KERNEL, [0, 1])
    assert red.window_s == pytest.approx(100_000e-9)
    # device 0: [10, 25] + [30, 35] + [60035, 60045] = 15 + 5 + 10 ns
    assert red.busy_s[0] == pytest.approx(30e-9)
    assert red.busy_s[1] == pytest.approx(50e-9)
    assert red.kernel_s == pytest.approx(5e-9)
    idle = 1 - (30e-9 + 50e-9) / 2 / red.window_s
    assert idle == pytest.approx(1 - 40e-9 / 100_000e-9)
    gaps = dict(red.idle_gaps)
    # device 0's gap [35, 60035] and device 1's [50, 100000] are over
    # 50 us and centred in the host's _land span; averaged over devices
    assert gaps["$dispatch.py:145 _land"] == pytest.approx(
        (60_000 + 99_950) * 1e-9 / 2)
    assert "while.1" not in " ".join(n for n, _ in red.device_ops)


def test_only_the_used_devices_count():
    """Planes of devices the run did not use are left out of busy time,
    kernel time and the idle gaps; a used device with no plane is idle."""
    rec = hand_built()
    red = devtrace.reduce(rec, KERNEL, devices=[0])
    assert list(red.busy_s) == [0]
    assert red.busy_s[0] == pytest.approx(30e-9)
    red = devtrace.reduce(rec, KERNEL, devices=[1])
    assert list(red.busy_s) == [1] and red.kernel_s == 0
    red = devtrace.reduce(rec, KERNEL, devices=[0, 2])
    assert red.busy_s == pytest.approx({0: 30e-9, 2: 0.0})
    # device 2's whole window is one gap, centred in the _land span
    assert dict(red.idle_gaps)["$dispatch.py:145 _land"] == pytest.approx(
        (60_000 + 100_000) * 1e-9 / 2)


def test_leaves_drop_containers():
    evs = hand_built().devices[0]
    names = [e.name.split(" ")[0] for e in devtrace.leaves(evs)]
    assert names == ["%fusion.1", "%fusion.2", "%custom-call.3", "%fusion.1"]


def test_short_name():
    assert devtrace.short_name(
        "%fusion.713 = pred[49152]{0:T(1024)} fusion(pred[1,384] %a)") \
        == "fusion.713 pred[49152]"


RECORDED = Path(__file__).resolve().parent / "data" / "trace_bfc_x1.json.gz"


def _recorded():
    doc = json.loads(gzip.open(RECORDED, "rt").read())
    rec = Recording(
        devices={int(d): [Event(*e) for e in evs]
                 for d, evs in doc["devices"].items()},
        host=[Event(*e) for e in doc["host"]])
    return doc, rec


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    """A slice of a chip trace of the BFC cell: busy equals a union
    computed another way (a boolean timeline at 1 ns), the kernel is found
    by its name, and the idle share lies between 0 and 1."""
    doc, rec = _recorded()
    lo, hi = doc["window"]
    red = devtrace.reduce(rec, switch_decision.KERNEL, [0], window=(lo, hi))
    evs = devtrace.leaves(rec.devices[0])
    line = np.zeros(int(hi - lo), bool)
    for e in evs:
        a, b = max(e.start_ns, lo) - lo, min(e.end_ns, hi) - lo
        if b > a:
            line[int(a):int(b)] = True
    assert red.busy_s[0] == pytest.approx(line.sum() / 1e9, rel=1e-3)
    assert red.kernel_s > 0
    assert red.kernel_s == pytest.approx(doc["kernel_s"], rel=1e-6)
    idle = 1 - red.busy_s[0] / red.window_s
    assert 0 < idle < 1
