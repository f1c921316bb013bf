"""ingest.store_ms: ``store.add_batch`` a drain (the float32 upcast and
int4 quantization of the cached states and embeddings, and the slab
writes), milliseconds, from the program's span."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.ms(ctx, "ingest", "store.add_batch")
