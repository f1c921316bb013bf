"""query.scan_ms: the store scan in round 1 (``store.search_batch``: the
bank refresh, the int4 scan, the top-k down), milliseconds a drain, from
the program's span."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.ms(ctx, "query", "store.search_batch")
