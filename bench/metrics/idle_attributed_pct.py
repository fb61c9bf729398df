"""Share of the device's idle time in the traced window, in percent, that
some Engine host span covers once the spans are put on the trace's clock
(``hostspans``): how much of the idle time the spans can name. None where
the run armed no span log or took no trace."""
import hostspans


def read(ctx):
    mapped, _ = hostspans.mapped(ctx)
    return None if mapped is None else hostspans.idle_attributed_pct(ctx.trace, mapped)
