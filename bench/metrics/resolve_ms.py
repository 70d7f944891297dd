"""Resolve per request, in ms: the mean of the program's
``serving.resolve`` spans, the replica ticket's resolve and its done
callbacks (the router's, which resolve the client's ticket) on the scan
stage, while the device waits for the next dispatch."""

from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serving.resolve")
