"""Host milliseconds per dispatched micro-batch in one phase of the
Engine's flush, numpy concat and zero-pad of the requests' frames: the
Engine's ``pack_s`` counter over ``n_batches``, over the untraced part
of the window."""
from hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "pack_s")
