"""The switch decision's least time on this chip (its bytes and operations
from `switch_decision.switch_decision_cost`, against the peaks table) as a
share of the kernel's measured time, in percent."""
import switch_decision


def read(ctx):
    kernel_s = ctx.reduction.kernel_s
    if kernel_s <= 0:
        return None
    n_bytes, n_ops = switch_decision.switch_decision_cost(
        ctx.n_ports, ctx.n_queues)
    least, bound = switch_decision.least_seconds(
        n_bytes * ctx.lane_ticks, n_ops * ctx.lane_ticks, ctx.peaks)
    ctx.notes["kernel.switch_decision_roofline.bound"] = bound
    return 100.0 * least / kernel_s
