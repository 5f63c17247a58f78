"""The decode tick: the share of the attention layers' allocated KV rows
that the ticks read, in percent.  It is the program's own count
(``repro.serve.engine.kv_rows_read``, which also gives
``ServeReport.kv_read_share``) over the live slots of every tick, which the
record's requests give: a request served ``tokens`` tokens was live in
``tokens - 1`` ticks, at positions ``prompt_len`` onward.  The program's
configuration comes from the driver that the cell's own file names.  A
program without that count, whose tick reads every row, reads nothing
here."""
import glob
import json
import os

from bench.harness import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _driver(config_name):
    """The driver of the first cell that runs this configuration."""
    for path in sorted(glob.glob(os.path.join(ROOT, "bench", "workloads",
                                              "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if cell["config"] == config_name:
            return cell["driver"]
    return None


def read(rec):
    if rec["kind"] != "serve" or not rec.get("ticks") or rec["chips"] != 1:
        return None
    try:
        from repro.serve.engine import kv_layers, kv_rows_read
    except ImportError:
        return None
    driver = _driver(rec["config"]["name"])
    if driver is None:
        return None
    cfg = load_module("drivers", driver, ROOT).program_config(rec["config"])
    positions = [r["prompt_len"] + j for r in rec["requests"]
                 for j in range(r["tokens"] - 1)]
    rows, alloc = kv_rows_read(kv_layers(cfg, rec["max_len"]),
                               rec["num_slots"], positions, rec["ticks"])
    return 100.0 * rows / alloc if alloc else None
