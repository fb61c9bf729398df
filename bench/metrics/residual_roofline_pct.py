"""The residual kernels' share of their roofline, in percent.

Their events are the trace ops named
``%stream_conv_residual_pallas_l<first>_<last>...``: one kernel per fusion
group that carries residual joins, named after the conv layers it
computes. Each processes as many frames as the leading dimension of its
output shape. Every call that starts in the window is priced by the
family's ``group_ops`` and ``group_bytes`` for its layers, projections
included; the least time is ``max(ops / peak, bytes / bandwidth)``
(``costs.roofline_s``) over the summed operations and bytes, and the
share is that over the kernels' summed device time. Which bound applied
goes to standard error."""
import re
import sys

import costs

PATTERN = r"^%stream_conv_residual_pallas_l\d+_\d+\b"
_LAYERS = re.compile(r"^%stream_conv_residual_pallas_l(\d+)_(\d+)\b")
_FRAMES = re.compile(r"= *[a-z0-9]+\[(\d+),")


def read(ctx):
    if ctx.trace is None or not hasattr(ctx.family, "group_ops"):
        return None
    evs = ctx.trace.events(PATTERN)
    if not evs:
        return None
    topo = ctx.cfg["topology"]
    ops = n_bytes = busy = 0.0
    for start, end, name in evs:
        span, frames = _LAYERS.match(name), _FRAMES.search(name)
        if not frames:
            return None
        first, last, n = int(span.group(1)), int(span.group(2)), int(frames.group(1))
        ops += n * ctx.family.group_ops(topo, first, last)
        n_bytes += ctx.family.group_bytes(
            topo, first, last, n, ctx.cfg["kernel_act_bytes"]
        )
        busy += (end - start) / 1e9
    t_roof, bound = costs.roofline_s(ops, n_bytes, ctx.device_kind, ctx.cfg)
    print(f"residual_roofline bound={bound} calls={len(evs)} "
          f"kernel_s={busy!r} roofline_s={t_roof!r}", file=sys.stderr)
    return 100.0 * t_roof / busy
