"""95th percentile of every gap between two tokens of a request in the
window: the gaps that hold an admission's prefill."""
from readers import window_percentile


def read(ctx):
    return window_percentile(ctx, "itl", 95)
