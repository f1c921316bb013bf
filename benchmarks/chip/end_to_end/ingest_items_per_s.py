"""ingest_items_per_s: items of the drains started in the window, over the
time from the window's start to the end of the last of them."""


def read(ctx):
    if ctx["loop"] != "ingest":
        return None
    return ctx["units"] / ctx["window_s"]
