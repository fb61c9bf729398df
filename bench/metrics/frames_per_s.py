"""Frames of requests answered with logits and seen by the benchmark
inside the window, over the window's length."""


def read(ctx):
    return ctx.answered_frames(ctx.t0, ctx.t_end) / ctx.seconds
