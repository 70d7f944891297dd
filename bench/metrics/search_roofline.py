"""Share of the roofline reached by the served search, in %.

The least time the chip could take for the requests completed in the
traced window (``work/<family>.py``: operations over peak rates, or the
bytes over peak bandwidth, whichever is larger, spread over the chips),
over the device's busy time in that window: the union of every device
operation's interval, averaged over the chips. Busy time counts every
operation, not one kernel's events, so a relayout copy or a later
replacement of a kernel counts too.
"""


def read(ctx):
    if ctx.n_requests == 0 or ctx.busy_s <= 0:
        return None
    return 100.0 * ctx.least_s / ctx.busy_s
