"""The whole window's share of the chip's bf16 peak: the model FLOPs of
every prefill and every decoded token, over the traced window times peak."""
from bench.peaks import peaks


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr or tr["window_s"] <= 0:
        return None
    f, cfg = rec["flops"], rec["config"]
    flops = 0
    for r in rec["requests"]:
        flops += f["prefill"](cfg, r["prompt_len"])
        flops += sum(f["decode"](cfg, r["prompt_len"] + j)
                     for j in range(1, r["tokens"]))
    peak = peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * flops / (tr["window_s"] * rec["chips"] * peak)
