"""Tiny cells of the chip benchmark, added by files alone, for CPU tests.

``make_root(tmp)`` copies the benchmark into ``tmp``, adds a tiny round
cell and a tiny serving cell (their configurations, mixes and cell files)
and lists them, with the metrics, in a ``BENCHMARK.json`` of its own.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROUND_CELL = "round.tiny"
ROUND4_CELL = "round.tiny.4chip"
SERVE_CELL = "serve.tiny"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp, *, int8: bool = True, limits=None) -> str:
    root = str(tmp)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    _write(root, "bench/configs/femnist-tiny.json", {
        "name": "femnist-tiny", "reference": "femnist-cnn", "width": 4,
        "num_classes": 62})
    _write(root, "bench/traffic/tiny.json", {
        "generator": "community", "num_clients": 12, "mean_samples": 20,
        "alpha": 0.5, "noise": 0.35, "test_size": 8,
        "round": {"active_proportion": 0.5, "committee_fraction": 0.4,
                  "k_updates": 3, "local_steps": 4, "local_batch": 16,
                  "local_lr": 0.05, "momentum": 0.9, "val_batch": 64,
                  "accept_threshold": 0.5, "weight_by_score": True,
                  "aggregation": "fedavg", "quantize_chain": int8,
                  "use_kernels": int8}})
    _write(root, f"bench/workloads/{ROUND_CELL}.json", {
        "config": "femnist-tiny", "traffic": "tiny", "chips": 1,
        "driver": "round", "limits": dict(limits or {
            "update_diff": 1e-3, "update_gap": 1e-3, "score_diff": 1e-3,
            "model1_diff": 1e-2, "change_gap_median": 1e-2})})
    _write(root, f"bench/workloads/{ROUND4_CELL}.json", {
        "config": "femnist-tiny", "traffic": "tiny8", "chips": 4,
        "driver": "round", "limits": dict(limits or {
            "update_diff": 1e-3, "update_gap": 1e-3, "score_diff": 1e-3,
            "model1_diff": 1e-2, "change_gap_median": 1e-2})})
    tiny8 = json.load(open(os.path.join(root, "bench/traffic/tiny.json")))
    tiny8["round"]["quantize_chain"] = tiny8["round"]["use_kernels"] = True
    _write(root, "bench/traffic/tiny8.json", tiny8)
    _write(root, "bench/configs/olmo-tiny.json", {
        "name": "olmo-tiny", "reference": "olmo-1b", "program": "olmo-1b",
        "program_overrides": {"d_model": 128, "num_units": 2, "num_heads": 4,
                              "num_kv_heads": 4, "d_ff": 256,
                              "vocab_size": 4096},
        "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 256,
        "vocab_size": 4096, "layer_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "serving": {"num_slots": 4,
                                                 "max_len": 48}})
    _write(root, "bench/traffic/tiny-poisson.json", {
        "generator": "poisson", "rate": 12.0, "prompt_buckets": [8, 16],
        "prompt_weights": [0.5, 0.5], "output_median": 20,
        "output_sigma": 0.5, "output_min": 8, "output_max": 32})
    _write(root, f"bench/workloads/{SERVE_CELL}.json", {
        "config": "olmo-tiny", "traffic": "tiny-poisson", "chips": 1,
        "driver": "serve", "check_requests": 12,
        "limits": {"logit_gap": 1e-3, "kv_diff": 1e-3}})
    bench["workloads"] += [
        {"name": ROUND_CELL, "config": "femnist-tiny", "traffic": "tiny",
         "chips": 1, "why": "tiny round cell for CPU tests"},
        {"name": ROUND4_CELL, "config": "femnist-tiny", "traffic": "tiny8",
         "chips": 4, "why": "tiny sharded round cell for CPU tests"},
        {"name": SERVE_CELL, "config": "olmo-tiny", "traffic": "tiny-poisson",
         "chips": 1, "why": "tiny serving cell for CPU tests"}]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                kind = ("round" if any(w.startswith("round.")
                                       for w in m["workloads"]) else "serve")
                m["workloads"] += ([ROUND_CELL, ROUND4_CELL] if kind == "round"
                                   else [SERVE_CELL])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root
