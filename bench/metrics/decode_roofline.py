"""The decode tick: the least time the chip could take for the bytes the
algorithm needs (all weights, and the keys and values of the positions in
use in each slot) and its FLOPs, over the tick programs' device time."""
from bench.peaks import peaks


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr:
        return None
    secs, calls = tr["modules"].get("jit_tick", (0.0, 0))
    if not calls or secs <= 0:
        return None
    f = rec["flops"]
    cfg = rec["config"]
    contexts = [r["prompt_len"] + j for r in rec["requests"]
                for j in range(1, r["tokens"])]
    nbytes = (calls * f["weight_bytes"]
              + f["kv_bytes_per_position"] * sum(contexts))
    flops = sum(f["decode"](cfg, c) for c in contexts)
    p = peaks(rec["device"]["kind"])
    least = max(nbytes / p["hbm_bytes_s"], flops / p["flops_bf16"])
    return 100.0 * least / secs
