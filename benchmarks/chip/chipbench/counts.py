"""Operations and bytes of the served work, counted from shapes.

A tower layer over S tokens of width d with SwiGLU width f does
8 S d^2 (Q, K, V, O projections) + 4 S^2 d (QK^T and PV) + 6 S d f (gate,
up, down) FLOPs (a multiply-add counts 2). The round-1 scan of Q queries
over N int4 rows of width E does 2 Q N E operations and reads
N (E/2 + 4) bytes of bank (nibbles + one f32 scale per row) and
Q E 4 bytes of queries.
"""
from __future__ import annotations



def layer_flops(S: int, d: int, f: int) -> float:
    return 8.0 * S * d * d + 4.0 * S * S * d + 6.0 * S * d * f


def frontend_flops(t: dict, cfg: dict) -> float:
    """Per item: the patch projection (a token lookup costs none)."""
    return 0.0 if t["vocab"] else 2.0 * t["n_tokens"] * t["d_input"] * \
        t["d_model"]


def exit_head_flops(t: dict, cfg: dict) -> float:
    """Per item and exit: the d_model x E exit projection."""
    return 2.0 * t["d_model"] * cfg["model"]["embed_dim"]


def bank_capacity(rows: int, start: int = 64) -> int:
    """The store's slab capacity for ``rows`` rows: it starts at 64 and
    doubles, and the device bank mirrors it, so the scan reads all of it."""
    cap = start
    while cap < rows:
        cap *= 2
    return cap


def scan_work(Q: int, N: int, E: int):
    """(operations, bytes) of one exhaustive int4 scan."""
    return 2.0 * Q * N * E, N * (E / 2 + 4) + Q * E * 4.0


def least_time(ops: float, nbytes: float, peaks: dict):
    """(seconds, bound) of the roofline: the larger of ops at the int8 peak
    and bytes at HBM bandwidth."""
    t_ops = ops / peaks["int8_ops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8 compute") if t_ops >= t_mem else (t_mem, "HBM")

