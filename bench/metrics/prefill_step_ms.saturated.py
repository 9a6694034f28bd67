"""Mean device time of one `jit_prefill_all` call, in ms."""
from bench import layers


def read(ctx):
    return layers.mean_call_ms(ctx, layers.PREFILL)
