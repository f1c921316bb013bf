"""query.filter_ms: round 1 per drain (query tower + fused store scan):
the program's ``RetrievalResult.per_round_s["filter"]`` times the batch,
averaged over the window's drains."""


def read(ctx):
    if ctx["loop"] != "query":
        return None
    return ctx["work"]["per_round_ms"]["filter"]
