"""Mean host time of one `Engine.step` in the window: its `serve.step` span
less the `serve.*.wait` spans inside it, in ms."""
from bench import spans


def read(ctx):
    return spans.step_host_ms(ctx)
