"""The chip benchmark's harness on the CPU: finding cells, configurations,
mixes and metrics by name, ``BENCHMARK.json``'s shape, the operation and
byte counts against hand counts, and the trace reduction on a small
recorded trace."""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import harness, kernels, trace  # noqa: E402

BENCH = harness.load_benchmark(REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in BENCH["paths"])
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    spec = harness.cell_spec(cell, BENCH, REPO)
    config = harness.load_json("configs", spec["config"], REPO)
    traffic = harness.load_json("traffic", spec["traffic"], REPO)
    harness.load_module("configs", config.get("reference", config["name"]), REPO)
    harness.load_module("traffic", traffic["generator"], REPO)
    harness.load_module("drivers", spec["driver"], REPO)
    assert NAME.match(cell) and spec["chips"] in (1, 4)
    assert set(spec["limits"]) and all(v > 0 for v in spec["limits"].values())
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(entry["why"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_found_by_name(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert callable(harness.load_module("metrics", metric, REPO).read)
    assert NAME.match(metric) and m["better"] in ("lower", "higher")
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    if metric.endswith("_roofline") or "mfu" in metric:
        assert m["unit"] == "%"
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_and_two_kinds(cell):
    e2e = [m["name"] for m in harness.metric_entries(BENCH, cell, False)]
    layers = harness.metric_entries(BENCH, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layers


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        harness.cell_spec("no.such.cell", BENCH, REPO)
    with pytest.raises(KeyError):
        harness.load_module("metrics", "no_such_metric", REPO)
    with pytest.raises(ValueError):
        harness.load_json("configs", "../BENCHMARK", REPO)


def test_femnist_cnn_flops_against_hand_count():
    cnn = harness.load_module("configs", "femnist-cnn", REPO)
    # conv1 2*784*9*32, conv2 2*196*288*64, fc1 2*3136*128, fc2 2*128*62
    assert cnn.forward_flops_per_image(32) == 8_495_616
    assert cnn.train_flops_per_image(32) == 3 * 8_495_616


def test_olmo_counts_against_hand_count():
    olmo = harness.load_module("configs", "olmo-1b", REPO)
    c = harness.load_json("configs", "olmo-1b", REPO)
    assert olmo.layer_params(c) == 4 * 2048 ** 2 + 3 * 2048 * 8192
    # 1,176,764,416 parameters in float32
    assert olmo.weight_bytes(c) == 4 * 1_176_764_416
    assert olmo.kv_bytes_per_position(c) == 4 * 2 * 16 * 2048
    per_layer = 2 * (4 * 2048 ** 2 + 3 * 2048 * 8192)
    assert olmo.decode_flops(c, 1) == 16 * (per_layer + 4 * 2048) \
        + 2 * 2048 * 50304
    # 3 tokens: 6 causal pairs
    assert olmo.prefill_flops(c, 3) == 16 * (3 * per_layer + 4 * 2048 * 6) \
        + 2 * 2048 * 50304


def test_kernel_byte_models():
    # D = 428,350 pads to 210 tiles of 2048 = 430,080 lanes
    assert kernels.padded(428_350) == 430_080
    nbytes, flops = kernels.fused_agg_bytes(24, 428_350)
    assert nbytes == 24 * 430_080 + 4 * 24 * 210 + 4 * 24 + 4 * 430_080
    assert flops == 2 * 24 * 430_080
    nbytes, _ = kernels.quantize_stack_bytes(24, 428_350)
    assert nbytes == 4 * 24 * 430_080 + 24 * 430_080 + 4 * 24 * 210
    # four chips: padded to 53 blocks of 4 * 2048 lanes, each chip a quarter
    assert kernels.padded(428_350, 4) == 434_176
    per_chip, _ = kernels.fused_agg_bytes(36, 428_350, 4)
    d = 434_176 // 4
    assert per_chip == 36 * d + 4 * 36 * (d // 2048) + 4 * 36 + 4 * d


def test_roofline_peaks_unknown_device_raises():
    rec = {"device": {"kind": "TPU v5 lite"}}
    assert kernels.roofline(rec, 819e9, 0, 1.0) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        kernels.roofline({"device": {"kind": "cpu"}}, 1, 1, 1.0)


def test_trace_reduction_synthetic():
    ops = [("%fusion.1 = f32[8] fusion()", 0, 10), ("%fusion.2 = f32[8] x", 5, 20),
           ("%all-gather.3 = f32[8] all-gather()", 30, 40),
           ("%while.1 = (s32[]) while()", 50, 90), ("%dot.1 = f32 dot()", 60, 70)]
    mods = [("jit_a(12)", 0, 20), ("jit_b(34)", 30, 40), ("jit_a(12)", 50, 90)]
    host = [("window", 0, 100), ("round", 0, 100),
            ("stage.local_trainer", 15, 45), ("np.asarray(jax.Array)", 22, 28)]
    r = trace.reduce([{"ops": ops, "modules": mods}], host, (0, 100))
    assert r["busy_s"] == pytest.approx(70e-9)      # [0,20] [30,40] [50,90]
    assert r["collective_s"] == pytest.approx(10e-9)
    assert r["modules"]["jit_a"] == [pytest.approx(60e-9), 2]
    assert r["device_ops"][0][0] == "jit_a"
    # three gaps of 10 ns: [20,30] [40,50] [90,100], longest first, ties by start
    assert [name for name, _ in r["idle_gaps"]] == [
        "stage.local_trainer > np.asarray(jax.Array)", "stage.local_trainer",
        "round"]
    assert all(s == pytest.approx(10e-9) for _, s in r["idle_gaps"])


def test_trace_reduction_recorded():
    path = os.path.join(REPO, "bench", "testdata", "trace_small.json")
    with open(path) as f:
        data = json.load(f)
    dev = {k: [tuple(e) for e in v] for k, v in data["devices"][0].items()}
    host = [tuple(e) for e in data["host"]]
    r = trace.reduce([dev], host, tuple(data["window"]))
    lo, hi = data["window"]
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in r["idle_gaps"]) <= idle + 1e-12
    # recorded values: a 15.68 ms prefill and 34.10 ms busy in 237 ms
    assert r["modules"]["jit_prefill_tok"] == [pytest.approx(0.015681321), 1]
    assert r["busy_s"] == pytest.approx(0.034104187)
    assert r["idle_gaps"][0][1] == pytest.approx(0.176677601)
    names = {h[0] for h in host}
    assert all(g.split(" > ")[-1] in names or g.startswith("host:")
               for g, _ in r["idle_gaps"])


def test_poisson_mix_fixes_the_work():
    gen = harness.load_module("traffic", "poisson", REPO)
    mix = harness.load_json("traffic", "steady", REPO)
    a = gen.make_requests(mix, 1, 20.0, 50304)
    b = gen.make_requests(mix, 2 ** 31 + 7, 20.0, 50304)
    assert len(a) == len(b) == round(mix["rate"] * 20)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.arrival for r in a] != [r.arrival for r in b]
    assert all(0 <= r.arrival < 20 for r in a)
    assert all(16 <= r.max_new <= 256 and r.prompt_len <= 768 for r in a)
    assert gen.apportion(10, [0.15, 0.25, 0.30, 0.20, 0.10]) == [1, 3, 3, 2, 1]


def test_community_generator_is_seeded():
    gen = harness.load_module("traffic", "community", REPO)
    mix = dict(harness.load_json("traffic", "f32.c100", REPO), num_clients=6)
    a, b = gen.make_dataset(mix, 3), gen.make_dataset(mix, 3)
    assert a.num_clients == 6
    assert all((x == y).all() for x, y in zip(a.client_images, b.client_images))
    assert a.client_images[0].shape[1:] == (28, 28, 1)
    labels = a.client_labels[0]
    assert labels.dtype.name == "int32" and labels.min() >= 0 and labels.max() < 62
