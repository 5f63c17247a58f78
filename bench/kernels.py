"""Operations and bytes of the round's kernels, from their shapes, and
their roofline share.

The byte models follow the program's own (``benchmarks/kernel_bench.py``
``_fused_bytes``, ``benchmarks/round_bench.py`` ``_stage_bytes``), kept
here so that no change to the program can move them.  Rows are tiled in
2048-lane blocks with one f32 scale per row and block; a sharded program
pads the width to a multiple of 2048 times the chips and each chip holds
its share.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from bench.peaks import peaks

TILE = 2048


def padded(dim: int, chips: int = 1) -> int:
    chunk = TILE * chips
    return dim + (-dim) % chunk


def fused_agg_bytes(K: int, dim: int, chips: int = 1) -> Tuple[int, int]:
    """(bytes, FLOPs) per chip of one fused int8 fedavg over a (K, D) stack:
    the int8 stack, its scales and the weights read, the f32 result
    written; a multiply and an add per element of the stack."""
    d = padded(dim, chips) // chips
    nblk = d // TILE
    nbytes = K * d + 4 * K * nblk + 4 * K + 4 * d
    return nbytes, 2 * K * d


def quantize_stack_bytes(K: int, dim: int, chips: int = 1) -> Tuple[int, int]:
    """(bytes, FLOPs) per chip of quantizing a (K, D) f32 stack: the stack
    read, int8 codes and one f32 scale per row and tile written; an abs,
    a max, a divide and a round per element."""
    d = padded(dim, chips) // chips
    nblk = d // TILE
    nbytes = 4 * K * d + K * d + 4 * K * nblk
    return nbytes, 4 * K * d


def kernel_time(rec: Dict, programs: Sequence[str]) -> Optional[float]:
    """Device seconds per call of the first of ``programs`` in the trace."""
    tr = rec.get("trace")
    if not tr:
        return None
    for name in programs:
        secs, calls = tr["modules"].get(name, (0.0, 0))
        if calls:
            return secs / calls
    return None


def roofline(rec: Dict, nbytes: int, flops: int, seconds: float) -> float:
    """The least time for these bytes and FLOPs on this chip, over the
    time taken, in %."""
    p = peaks(rec["device"]["kind"])
    least = max(nbytes / p["hbm_bytes_s"], flops / p["flops_bf16"])
    return 100.0 * least / seconds
