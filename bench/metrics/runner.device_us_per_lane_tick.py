"""Device time of the traced call (union of operation intervals, summed
over devices) per simulated lane-tick, in microseconds."""


def read(ctx):
    return sum(ctx.reduction.busy_s.values()) * 1e6 / ctx.lane_ticks
