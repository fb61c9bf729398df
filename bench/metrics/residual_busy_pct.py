"""The residual kernels' device time over all device busy time, in
percent: how much of the device's work the other ops (the frame's
transfer layout, quantize and pad, the stem's kernel, the head and the
output check) take. The kernels are the trace ops named
``%stream_conv_residual_pallas_l<first>_<last>...``."""
PATTERN = r"^%stream_conv_residual_pallas_l\d+_\d+\b"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = ctx.trace.events(PATTERN)
    busy = ctx.trace.busy_s()
    if not evs or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e, _ in evs) / 1e9 / busy
