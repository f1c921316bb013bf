"""idle_share.query: 1 - (union of device op intervals) / (traced window),
from the profiler trace of a query window."""


def read(ctx):
    if ctx["loop"] != "query" or ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
