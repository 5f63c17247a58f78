"""The fused int8 aggregation kernel (``kernels/fused_agg.py``): the least
time the chip could take for its bytes (the int8 stack, its scales and
weights read, the f32 result written; bandwidth bounds it) over the
kernel program's device time, per call and per chip."""
from bench.kernels import fused_agg_bytes, kernel_time, roofline

PROGRAMS = ("jit_fused_agg_kernel", "jit_aggregate_sharded")


def read(rec):
    if rec["kind"] != "round" or not rec["int8"]:
        return None
    t = kernel_time(rec, PROGRAMS)
    if t is None:
        return None
    nbytes, flops = fused_agg_bytes(rec["K"], rec["dim"], rec["chips"])
    return roofline(rec, nbytes, flops, t)
