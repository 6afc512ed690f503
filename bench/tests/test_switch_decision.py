"""Hand-checked work count of the switch decision, and the roofline."""
import pytest

import switch_decision


@pytest.mark.parametrize("p, q, n_bytes, n_ops", [
    # bytes: 9 * 384 * 32 = 110 592, + 14 * 384 = 5 376
    # ops:  13 * 384 * 32 = 159 744, + 6 * 384 = 2 304
    (384, 32, 115_968, 162_048),
    # bytes: 9 * 98 * 32 = 28 224, + 14 * 98 = 1 372
    # ops:  13 * 98 * 32 = 40 768, + 6 * 98 = 588
    (98, 32, 29_596, 41_356),
])
def test_cost(p, q, n_bytes, n_ops):
    assert switch_decision.switch_decision_cost(p, q) == (n_bytes, n_ops)


def test_least_seconds_is_memory_bound_on_v5e():
    import json
    from conftest import BENCH
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    n_bytes, n_ops = switch_decision.switch_decision_cost(384, 32)
    t, bound = switch_decision.least_seconds(n_bytes, n_ops, peaks)
    assert bound == "memory"
    assert t == pytest.approx(115_968 / 819e9)


def test_kernel_pattern():
    # as recorded on a TPU v5e: the kernel, and another custom call
    name = ('%_fused.9 = (s32[512,1]{1,0:T(8,128)}, s32[512,32]{1,0:T(8,128)})'
            ' custom-call(s32[512,32]{1,0:T(8,128)S(1)} %copy-done.80), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}')
    assert switch_decision.KERNEL.search(name)
    assert not switch_decision.KERNEL.search(
        '%custom-call.272 = s32[512,1,3]{2,1,0:T(1,128)} custom-call(), '
        'custom_call_target="AllocateBuffer"')
    assert not switch_decision.KERNEL.search(
        "%fusion.713 = pred[49152]{0} fusion(pred[1,384,4,256] %a)")
