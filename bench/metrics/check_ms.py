"""Host milliseconds per dispatched micro-batch in one phase of the
Engine's flush, the ``isfinite`` sync on the logits: the Engine's
``check_s`` counter over ``n_batches``, over the untraced part of the
window."""
from hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "check_s")
