"""A tiny hybrid serving cell of the chip benchmark, added by files alone,
for CPU tests.

``make_root(tmp)`` builds ``tiny.make_root``'s copy of the benchmark and
adds a tiny Jamba cell (two periods of Mamba, Mamba, attention, Mamba; its
configuration, mix and cell file), listed with the serving metrics in that
copy's ``BENCHMARK.json``.
"""
from __future__ import annotations

import json
import os

import tiny

HYBRID_CELL = "serve.tiny.hybrid"


def make_root(tmp) -> str:
    root = tiny.make_root(tmp)
    tiny._write(root, "bench/configs/jamba-tiny.json", {
        "name": "jamba-tiny", "reference": "jamba2-3b", "program": "jamba2-3b",
        "program_overrides": {
            "d_model": 64, "num_units": 2, "num_heads": 4, "num_kv_heads": 1,
            "d_ff": 128, "vocab_size": 512, "mamba_dt_rank": 8,
            "unit": {"attn_period": 4, "attn_offset": 2, "expert_period": 2,
                     "expert_offset": 1, "moe": False}},
        "attn_layer_offset": 2, "attn_layer_period": 4,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_size": 64, "intermediate_size": 128, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
        "num_attention_heads": 4, "num_experts": 1, "num_hidden_layers": 8,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "vocab_size": 512,
        "serving": {"num_slots": 4, "max_len": 136}})
    tiny._write(root, "bench/traffic/tiny-longdoc.json", {
        "generator": "poisson", "rate": 10.0, "prompt_buckets": [37, 100],
        "prompt_weights": [0.5, 0.5], "output_median": 20,
        "output_sigma": 0.5, "output_min": 8, "output_max": 32})
    tiny._write(root, f"bench/workloads/{HYBRID_CELL}.json", {
        "config": "jamba-tiny", "traffic": "tiny-longdoc", "chips": 1,
        "driver": "serve_hybrid", "check_requests": 4,
        "limits": {"logit_gap": 1e-3, "kv_diff": 1e-3, "state_diff": 1e-3}})
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append(
        {"name": HYBRID_CELL, "config": "jamba-tiny",
         "traffic": "tiny-longdoc", "chips": 1,
         "why": "tiny hybrid serving cell for CPU tests"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if tiny.SERVE_CELL in m.get("workloads", ()):
                m["workloads"].append(HYBRID_CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root
