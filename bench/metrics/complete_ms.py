"""Host milliseconds per dispatched micro-batch in one phase of the
Engine's flush, the scatter of the logits to the requests (after each
request's ``done_at`` stamp, so outside ``busy_s``): the Engine's
``complete_s`` counter over ``n_batches``, over the untraced part of the
window."""
from hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "complete_s")
