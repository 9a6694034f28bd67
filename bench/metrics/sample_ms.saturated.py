"""Mean per decode step of the logits fetch and the host sampling
(`serve.decode.fetch` + `serve.decode.sample`), in ms."""
from bench import spans


def read(ctx):
    return spans.sample_ms(ctx)
