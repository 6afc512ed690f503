"""Share of the traced call in which no operation ran on the device,
averaged over the cell's devices."""


def read(ctx):
    red = ctx.reduction
    busy = sum(red.busy_s.values()) / len(red.busy_s)
    return 1.0 - busy / red.window_s
