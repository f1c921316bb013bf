"""ingest.tower_ms: the tower's share of a drain, from the program's
spans: ``engine.superficial`` (the items up, the superficial pass, its
states down) plus ``engine.continue`` (the states up, the continuation to
the exit, the embeddings down), milliseconds a drain."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.ms(ctx, "ingest", "engine.superficial", "engine.continue")
