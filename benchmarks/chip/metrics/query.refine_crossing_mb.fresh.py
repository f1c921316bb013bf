"""query.refine_crossing_mb.fresh: bytes between host and device, both
ways, under round 3's ``query.refine`` (the program's counters at each
crossing), MB a drain."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.crossing_mb(ctx, "query", "query.refine")
