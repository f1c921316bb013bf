"""query.mfu: per drain, the least time the chip needs for its model work
(query tower and refine FLOPs at the bf16 peak, the scan's operations at
the int8 peak), summed over the window's drains and divided by the window
time."""


def read(ctx):
    if ctx["loop"] != "query":
        return None
    return 100.0 * ctx["work"]["mfu"]
