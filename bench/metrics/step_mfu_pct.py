"""The whole step's share of the chip's peak, in percent: frames answered
per second (host clock, untraced part of the window) times the step's
operations per frame (conv stack and head, ``costs.frame_ops``) over the
peak that prices the configuration (``costs.peak_ops_per_s``)."""
import costs


def read(ctx):
    span = ctx.t_split - ctx.t0
    frames = ctx.answered_frames(ctx.t0, ctx.t_split)
    if span <= 0 or not frames:
        return None
    ops_per_s = frames / span * costs.frame_ops(ctx.cfg["topology"])
    return 100.0 * ops_per_s / costs.peak_ops_per_s(ctx.device_kind, ctx.cfg)
