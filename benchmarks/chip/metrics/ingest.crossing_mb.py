"""ingest.crossing_mb: bytes between host and device, both ways, under
``engine.drain`` (the program's counters at each crossing), MB a drain."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.crossing_mb(ctx, "ingest", "engine.drain")
