"""queries_per_s: queries of the drains started in the window, over the
time from the window's start to the end of the last of them."""


def read(ctx):
    if ctx["loop"] != "query":
        return None
    return ctx["units"] / ctx["window_s"]
