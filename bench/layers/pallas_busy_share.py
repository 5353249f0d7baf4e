"""Share of the device's busy time spent in Pallas (Mosaic) kernels."""


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.mosaic_s / t.busy_s
