"""idle_share.ingest: 1 - (union of device op intervals) / (traced window),
from the profiler trace of an ingest window."""


def read(ctx):
    if ctx["loop"] != "ingest" or ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
