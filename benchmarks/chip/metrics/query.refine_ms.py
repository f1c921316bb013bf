"""query.refine_ms: round 3 per drain (fetching the candidates' stored
embeddings, and for coarse candidates the cached state, the continuation
and the upgrade): ``per_round_s["refine"]`` times the batch, averaged over
the window's drains."""


def read(ctx):
    if ctx["loop"] != "query":
        return None
    return ctx["work"]["per_round_ms"]["refine"]
