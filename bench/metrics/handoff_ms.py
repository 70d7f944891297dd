"""Hand-off per request, in ms: the mean of the program's
``serving.handoff`` spans, from the encode stage putting the codes on
the scan stage's queue to the scan stage starting their dispatch (its
wait for a ``dispatch_ahead`` slot included)."""

from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serving.handoff")
