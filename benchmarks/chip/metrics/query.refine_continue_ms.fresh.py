"""query.refine_continue_ms.fresh: round 3's continuation
(``engine.refine_continue``: the stacked cached states up, layers
[N, depth), the embeddings down), milliseconds a drain, from the
program's span."""
from chipbench import program_spans as PS


def read(ctx):
    return PS.ms(ctx, "query", "engine.refine_continue")
