"""The router's host time per request, in ms: the self time of the
program's ``proxy.submit`` spans (``QueryRouter.submit``, entry to
return), less the ``serving.admit`` put inside each."""

from bench import spans


def read(ctx):
    return spans.self_mean_ms(ctx, "proxy.submit", "serving.admit")
