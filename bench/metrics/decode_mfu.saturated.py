"""Model FLOPs of the `decode_all` calls over their device time and the bf16
peak, in %."""
from bench import layers


def read(ctx):
    return layers.decode_mfu(ctx)
