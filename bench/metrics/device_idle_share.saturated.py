"""Share of the traced window in which no op ran on the device, mean over
devices, in %."""
from bench import layers


def read(ctx):
    return layers.idle_share(ctx)
