"""Host spans (``repro.tracing``) and the phase keys they leave in the
round pipeline's ``ctx.timings`` and the serving engine's ``host_s``."""
import time

import jax
import numpy as np
import pytest

from repro.api import build_runtime
from repro.configs import registry
from repro.data import make_femnist_like
from repro.fl import femnist_adapter
from repro.fl.pipeline import STAGE_TIMING_KEYS
from repro.models import init_model
from repro.serve import Request, ServeEngine, VirtualClock
from repro.tracing import span


def test_span_adds_seconds_under_key():
    into = {"a": 1.0}
    with span("x.a", into, "a"):
        time.sleep(0.01)
    assert 1.01 <= into["a"] < 2.0
    with span("x.b", into):
        pass
    assert set(into) == {"a", "x.b"} and into["x.b"] >= 0


def test_span_nests():
    into = {}
    with span("outer", into):
        with span("inner", into):
            time.sleep(0.005)
        with span("inner", into):
            pass
    assert into["inner"] >= 0.005
    assert into["outer"] >= into["inner"]


def test_span_without_into_records_nothing():
    with span("nothing"):
        pass
    into = {}
    with pytest.raises(ZeroDivisionError):
        with span("raises", into):
            1 / 0
    # a span that raises still counts its time, so buckets never go short
    assert set(into) == {"raises"}


@pytest.fixture(scope="module")
def ds():
    return make_femnist_like(num_clients=24, mean_samples=40,
                             test_size=200, seed=3)


ROUND_CFG = dict(active_proportion=0.5, committee_fraction=0.3,
                 k_updates=4, local_steps=2, local_batch=8, seed=0)


@pytest.mark.parametrize("schedule,int8", [
    ("sequential", False), ("sequential", True), ("async", True)])
def test_round_phase_keys(ds, schedule, int8):
    rt = build_runtime(femnist_adapter(width=8), ds,
                       dict(ROUND_CFG, quantize_chain=int8, use_kernels=int8),
                       schedule=schedule)
    rt.run_round()
    (t,) = rt.stage_timings
    assert {k for k in t if "." not in k} == set(STAGE_TIMING_KEYS)
    for k, v in t.items():
        assert k.split(".")[0] in STAGE_TIMING_KEYS, k
        assert v >= 0, k
    for k in ("train.batches", "train.dispatch", "train.unstack",
              "validate.batches", "validate.wait", "pack.chain",
              "aggregate.chain"):
        assert k in t, k
    phases = t["train.batches"] + t["train.dispatch"] + t["train.unstack"]
    assert phases <= t["train"] + 1e-6
    assert t["pack.chain"] <= t["pack"] + 1e-6
    assert t["aggregate.chain"] <= t["aggregate"] + 1e-6
    if schedule == "sequential":
        for bucket in STAGE_TIMING_KEYS:
            assert t[f"{bucket}.wait"] <= t[bucket] + 1e-6
    else:
        assert "reward.wait" in t


def test_engine_host_seconds_by_phase():
    cfg = registry.get_config(
        "olmo-1b", d_model=64, num_units=2, num_heads=2, num_kv_heads=2,
        d_ff=128, vocab_size=512,
    )
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, (s,)).astype(np.int32),
                    max_new=g, arrival=a)
            for i, (s, g, a) in enumerate([(8, 4, 0.0), (12, 1, 0.0),
                                           (8, 6, 20.0)])]
    eng = ServeEngine(cfg, params, num_slots=2, max_len=32)
    rep = eng.run(reqs, clock=VirtualClock())
    assert [len(r.tokens) for r in rep.results] == [4, 1, 6]
    for k in ("engine.admit", "engine.prefill", "engine.insert",
              "engine.tick", "engine.fetch", "engine.idle"):
        assert rep.host_s[k] >= 0, k
    assert set(rep.host_s) <= {"engine.admit", "engine.prefill",
                               "engine.insert", "engine.tick",
                               "engine.fetch", "engine.swap", "engine.idle"}
    assert rep.host_s["engine.prefill"] <= rep.host_s["engine.admit"]
    m = rep.metrics()
    assert m["engine.tick_ms_per_tick"] >= 0
