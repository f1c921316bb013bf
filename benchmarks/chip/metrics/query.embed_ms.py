"""query.embed_ms: the query tower in round 1 (``query.embed``: the
queries up, the all-exits pass, the embeddings down), milliseconds a
drain, from the program's span."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.ms(ctx, "query", "query.embed")
