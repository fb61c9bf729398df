"""Seconds from the start of the process to the start of the window:
imports, chip start-up, weights, frames, compiling the plan, the Engine's
self-check and warm-up probe, and the warm-up traffic."""


def read(ctx):
    return ctx.setup_s
