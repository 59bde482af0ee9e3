"""95th percentile of the time from due to first token, over every
request due in the window (recorded beside the judged median: with some
thirty requests a window, it is all but the largest)."""
from readers import window_percentile


def read(ctx):
    return window_percentile(ctx, "ttft", 95)
