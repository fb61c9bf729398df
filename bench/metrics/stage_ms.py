"""Host milliseconds per dispatched micro-batch in one phase of the
Engine's flush, the call that queues the asynchronous host-to-device
copy of the packed micro-batch into a fresh buffer: the Engine's
``stage_s`` counter over ``n_batches``, over the untraced part of the
window. The transfer itself, and its layout transpose, run inside the
forward call and so count in ``device_wait_ms``."""
from hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, "stage_s")
