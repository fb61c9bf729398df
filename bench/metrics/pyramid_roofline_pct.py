"""The fused conv pyramid kernel's share of its roofline, in percent.

Its events are the trace ops named ``%stream_conv_pyramid_pallas...`` (the
custom call the Pallas kernel becomes); each processes as many frames as
the leading dimension of its output shape. For the frames of the calls
that start in the window, the least time is ``max(ops / peak, bytes /
bandwidth)`` (``costs.roofline_s``, with ``costs.conv_ops`` and
``costs.conv_bytes``), and the share is that over the kernel's summed
device time. Which bound applied goes to standard error."""
import re
import sys

import costs

PATTERN = r"^%stream_conv_pyramid_pallas\b"
_FRAMES = re.compile(r"= *[a-z0-9]+\[(\d+),")


def read(ctx):
    if ctx.trace is None:
        return None
    evs = ctx.trace.events(PATTERN)
    if not evs:
        return None
    topo = ctx.cfg["topology"]
    ops = n_bytes = busy = 0.0
    for start, end, name in evs:
        m = _FRAMES.search(name)
        if not m:
            return None
        n = int(m.group(1))
        ops += n * costs.conv_ops(topo)
        n_bytes += costs.conv_bytes(topo, n, ctx.cfg["kernel_act_bytes"])
        busy += (end - start) / 1e9
    t_roof, bound = costs.roofline_s(ops, n_bytes, ctx.device_kind, ctx.cfg)
    print(f"pyramid_roofline bound={bound} calls={len(evs)} "
          f"kernel_s={busy!r} roofline_s={t_roof!r}", file=sys.stderr)
    return 100.0 * t_roof / busy
