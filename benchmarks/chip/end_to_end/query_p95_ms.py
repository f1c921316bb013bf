"""query_p95_ms: 95th percentile over every query answered in the window;
a query's latency runs from its drain's ``query_batch`` call to the
return."""
import numpy as np


def read(ctx):
    if ctx["loop"] != "query":
        return None
    return float(np.percentile(ctx["latencies_s"], 95)) * 1e3
