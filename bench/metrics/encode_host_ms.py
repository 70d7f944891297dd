"""Host time of the encode stage per request, in ms: the mean of the
program's ``serving.encode`` spans, the ``encode_fn`` call that
dispatches the jitted encoder."""

from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serving.encode")
