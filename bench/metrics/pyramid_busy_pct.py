"""The fused conv pyramid kernel's device time over all device busy time,
in percent: how much of the device's work the ops around the kernel
(input layout copies, quantize, pad, head, output check) take."""
PATTERN = r"^%stream_conv_pyramid_pallas\b"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = ctx.trace.events(PATTERN)
    busy = ctx.trace.busy_s()
    if not evs or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e, _ in evs) / 1e9 / busy
