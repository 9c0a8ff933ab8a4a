"""Bytes and operations of a kernel call, from its shapes alone.

The victim-order kernel (the program's fused eq.-16 scoring pass) streams
five ``(N,)`` columns (arrival rate, mean fetch latency, residual, size,
cached flag), each padded to whole ``(block // 128, 128)`` tiles of 4-byte
words, and writes the score column plus each block's ``top`` candidates
(values and indices) as lane-dense ``(8, 128)`` tiles.  Per element it
does the eq.-16 arithmetic and ``top`` masked-min extraction rounds.
"""
from __future__ import annotations

LANES, SUBLANES, WORD = 128, 8, 4

# eq. 16 per element: E[D] (3), Var[D] (9), sqrt, omega*std, E+omega*std,
# two clamps, the denominator product and the division, then the mask
SCORE_OPS = 20
# one extraction round per element: min, compare, select, min over the
# attaining indices, compare, re-mask
ROUND_OPS = 6


def victim_order_shapes(n: int, top: int = 8, block: int = 1024) -> dict:
    """The padded layout of one call over ``n`` objects."""
    rows = -(-n // LANES)
    brows = min(block // LANES, rows)
    npad = -(-rows // brows) * brows * LANES
    grid = npad // (brows * LANES)
    crows = SUBLANES * -(-top // (SUBLANES * LANES))
    return dict(n=n, npad=npad, grid=grid, cand=grid * crows * LANES,
                top=top)


def victim_order_cost(n: int, top: int = 8, block: int = 1024):
    """``(bytes, ops)`` of one victim-order call over ``n`` objects:
    bytes read and written by the kernel's operands, and the elementwise
    operations of scoring and extraction."""
    s = victim_order_shapes(n, top, block)
    read = 5 * s["npad"] * WORD + LANES * WORD          # streams + omega
    written = s["npad"] * WORD + 2 * s["cand"] * WORD   # scores + cands
    ops = s["npad"] * (SCORE_OPS + top * ROUND_OPS)
    return read + written, ops


def roofline_time(bytes_: float, ops: float, peak: dict):
    """Least time of one call on a chip, and which bound sets it."""
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["flops_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "ops")
