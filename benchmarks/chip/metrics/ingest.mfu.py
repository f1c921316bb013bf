"""ingest.mfu: the window's model FLOPs over the window time, as a share of
the chip's bf16 peak. FLOPs per item come from the shapes (``counts``):
the layers the engine reports having run (``EngineStats.layers_executed``:
the superficial prefix plus the continuation to each item's exit), the
patch projection and the exit head."""


def read(ctx):
    if ctx["loop"] != "ingest":
        return None
    return 100.0 * ctx["work"]["mfu"]
