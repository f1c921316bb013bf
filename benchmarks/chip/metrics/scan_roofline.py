"""scan_roofline: the int4 scan kernel's share of its roofline. For each
round-1 scan of the window the least time is the larger of 2 Q N E
operations at the int8 peak and N (E/2 + 4) + 4 Q E bytes at HBM
bandwidth (``counts.scan_work``); their sum over the kernel's device time
in the trace: the custom calls that read the int4 bank, an int8 operand of
shape (bank capacity, E / 2)."""
from chipbench import counts, trace


def read(ctx):
    if ctx["loop"] != "query" or ctx["trace"] is None:
        return None
    w = ctx["work"]
    bank = f"s8[{w['bank_capacity']},{w['embed_dim'] // 2}]"
    busy = trace.kernel_s(ctx["trace"], "custom-call", bank)
    if busy <= 0:
        return None
    least, _ = counts.least_time(w["scan_ops"], w["scan_bytes"],
                                 ctx["peaks"])
    return 100.0 * w["scan_calls"] * least / busy
