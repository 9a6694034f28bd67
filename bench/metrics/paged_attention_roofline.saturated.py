"""Least time of the paged-attention kernel's work over its device time inside
`decode_all`, in %."""
from bench import layers


def read(ctx):
    return layers.paged_attention_roofline(ctx)
