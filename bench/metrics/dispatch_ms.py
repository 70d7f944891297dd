"""Host time of the search dispatch per request, in ms: the mean of the
program's ``serving.dispatch`` spans, the ``search_fn`` call (the index's
Python and the jitted call) on the scan stage."""

from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serving.dispatch")
