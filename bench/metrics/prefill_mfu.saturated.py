"""Model FLOPs of the `prefill_all` calls (valid prompt rows) over their device
time and the bf16 peak, in %."""
from bench import layers


def read(ctx):
    return layers.prefill_mfu(ctx)
