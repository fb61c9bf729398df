"""Host milliseconds per dispatched micro-batch in one phase of the
Engine's flush, the forward, from its dispatch through
``block_until_ready``: the host-to-device transfer of the micro-batch
and its layout transpose, then the forward program. The Engine's
``device_wait_s`` counter over ``n_batches``, over the untraced part of
the window."""
from hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "device_wait_s")
