"""query.verify_ms: round 2 and the final match per drain, both on the
host: ``per_round_s["verify"] + per_round_s["match"]`` times the batch,
averaged over the window's drains."""


def read(ctx):
    if ctx["loop"] != "query":
        return None
    ms = ctx["work"]["per_round_ms"]
    return ms["verify"] + ms["match"]
