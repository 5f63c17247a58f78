"""The int8 stack codec kernel (``kernels/quantize.py``): the least time
the chip could take for its bytes (the f32 stack read, int8 codes and
scales written) over the kernel program's device time, per call and chip."""
from bench.kernels import kernel_time, quantize_stack_bytes, roofline

PROGRAMS = ("jit_quantize_stack_kernel", "jit_quantize_sharded")


def read(rec):
    if rec["kind"] != "round" or not rec["int8"]:
        return None
    t = kernel_time(rec, PROGRAMS)
    if t is None:
        return None
    nbytes, flops = quantize_stack_bytes(rec["K"], rec["dim"], rec["chips"])
    return roofline(rec, nbytes, flops, t)
