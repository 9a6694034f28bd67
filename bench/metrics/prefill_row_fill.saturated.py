"""Prompt rows over rows computed by the window's `prefill_all` calls
(`rows_valid` / `rows_computed` of `serve.prefill.dispatch`), in %."""
from bench import spans


def read(ctx):
    return spans.prefill_row_fill(ctx)
