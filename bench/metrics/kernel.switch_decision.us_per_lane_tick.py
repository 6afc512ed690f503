"""Device time of the switch-decision kernel's events per simulated
lane-tick, in microseconds; nothing where the kernel did not run."""


def read(ctx):
    if ctx.reduction.kernel_s <= 0:
        return None
    return ctx.reduction.kernel_s * 1e6 / ctx.lane_ticks
