"""Admission wait per request, in ms: the mean of the program's
``serving.queued`` spans, from a ticket entering a replica's admission
queue to the encode stage taking it out."""

from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serving.queued")
