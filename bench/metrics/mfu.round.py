"""The whole round's share of the chips' bf16 peak: the model FLOPs of
local training (forward and backward) and committee scoring (forward) in
the traced window, over the window times chips times peak.  Padding rows
of the sharded programs are not counted."""
from bench.peaks import peaks


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "round" or not tr or tr["window_s"] <= 0:
        return None
    calls = rec["calls"]
    flops = (calls.get("local_trainer", 0) * rec["P"] * rec["steps"]
             * rec["batch"] * rec["train_flops"]
             + calls.get("validator", 0) * rec["P"] * rec["Q"]
             * rec["val_batch"] * rec["fwd_flops"])
    peak = peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * flops / (tr["window_s"] * rec["chips"] * peak)
