"""Bring-up check: the BFLC round and the serving engine on one TPU chip,
through the entry points a user calls.

  python chip_smoke.py              # one chip: round phase, then serve phase
  python chip_smoke.py --chips 4    # the sharded round engine on four chips,
                                    # against the one-chip engine, both at
                                    # "highest" matmul precision

Round phase (the paper's path): ``repro.api.build_runtime`` with femnist-cnn
at its default width 32 on a 100-client community, with the sizes
``python -m repro.launch.train --driver fl`` defaults to.  Three rounds on the
f32 chain, then three on the int8 chain, whose committee scores, packs and
aggregates through the compiled Pallas kernels.  It checks the chain, that
test accuracy rises, and the kernels against the ``kernels/ref.py`` oracles
at the round's own D.

Serve phase: ``ServeEngine`` on olmo-1b at its published widths, weights
drawn from the seed.  Six requests over two prompt lengths must each return
their full token budget, and the prefill logits must be finite.

Times printed on the way are for information only.  The script exits
non-zero, and prints no result, when JAX finds no TPU; the last line of a
passing run is one JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 0
# the community `repro.launch.train --driver fl` defaults to
ROUND_CFG = dict(active_proportion=0.2, k_updates=8, local_steps=20,
                 seed=SEED)
INT8_CFG = dict(ROUND_CFG, quantize_chain=True, use_kernels=True)
ROUNDS = 3

# kernel outputs against the oracles: an int8 code may differ by one step
# (a tie at .5 can round either way), scales and f32 outputs by f32
# rounding of O(1) values (inputs are unit normals)
Q_STEP = 1
SCALE_RTOL = 1e-6
F32_ATOL = 1e-5

# four chips against one: score entries are accuracies over a validation
# batch of 64, so one flipped prediction moves an entry by 1/64; the
# aggregated parameters may differ by one int8 step of an input row
SCORE_ATOL = 2 / 64
PARAM_STEPS = 2
SHARDED_PRECISION = "highest"


def log(*a) -> None:
    print(*a, flush=True)


def require(ok: bool, what: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


# ----------------------------------------------------------------------
# round phase
# ----------------------------------------------------------------------
def make_dataset(num_clients: int = 100):
    from repro.data import make_femnist_like

    return make_femnist_like(num_clients=num_clients, mean_samples=80,
                             test_size=1000, seed=1)


class FirstScores:
    """A validator stage that keeps the first cohort's P x Q score matrix."""

    def __init__(self, name: str):
        from repro.fl.pipeline import VALIDATORS

        self.inner = VALIDATORS[name]
        self.first = None

    def prepare(self, ctx) -> None:
        self.inner.prepare(ctx)

    def __call__(self, ctx) -> None:
        import numpy as np

        self.inner(ctx)
        if self.first is None:
            self.first = np.asarray(ctx.cohort_scores)


def run_rounds(ds, cfg: dict, *, width: int, rounds: int, validator: str,
               mesh=None, label: str):
    """Runs ``rounds`` rounds; returns (runtime, FirstScores, accuracies)."""
    import jax

    from repro.api import build_runtime
    from repro.fl import femnist_adapter

    scores = FirstScores(validator)
    rt = build_runtime(femnist_adapter(width=width), ds, cfg,
                       stages={"validator": scores}, mesh=mesh)
    accs, secs = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        rt.run_round()
        jax.block_until_ready(rt.global_params())
        secs.append(time.perf_counter() - t0)
        accs.append(rt.evaluate())
    require(rt.chain.verify(), f"{label}: chain verification failed")
    steady = sum(secs[1:]) / max(1, len(secs) - 1)
    log(f"# {label}: first round {secs[0]:.3f} s (compile included), "
        f"steady {steady:.3f} s/round, test accuracy by round "
        f"{[round(a, 4) for a in accs]}, chain height {rt.chain.height}")
    return rt, scores, accs


def check_kernels(D: int, K: int = 8) -> None:
    """The int8 chain kernels at width D on the default device, against
    the ``kernels/ref.py`` oracles run on the host CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(SEED)
    stack = jax.random.normal(key, (K, D), jnp.float32)
    base = jax.random.normal(jax.random.fold_in(key, 1), (D,), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 2), (K,)))
    dpad = ops.padded_dim(D)

    q, s, _ = ops.quantize_stack(stack)
    agg = ops.aggregate_quantized(q, s, D, method="fedavg", weights=w)
    qo, so, _ = ops.aggregate_quantized(q, s, D, method="cwmed",
                                        quantize_out=True)
    cands = ops.candidates_from_quantized(base, q, s, D)
    got = {k: np.asarray(v) for k, v in dict(
        q=q, s=s, agg=agg, qo=qo, so=so, cands=cands).items()}

    wn = np.asarray(w)
    with jax.default_device(jax.devices("cpu")[0]):
        padded = np.pad(np.asarray(stack), ((0, 0), (0, dpad - D)))
        rq, rs = map(np.asarray, ref.quantize_stack_ref(padded))
        # the aggregation oracles read the kernel's own int8 stack, so they
        # check the reduction rather than the codec a second time
        ragg = np.asarray(
            ref.fused_agg_ref(got["q"], got["s"], wn / wn.sum()))[:D]
        rmed = ref.fused_agg_ref(got["q"], got["s"], wn, method="cwmed")
        rqo, rso = map(np.asarray, ref.quantize_ref(rmed))
        rcands = (np.asarray(ref.dequantize_stack_ref(got["q"], got["s"]))
                  + np.pad(np.asarray(base), (0, dpad - D))[None])[:, :D]

    def codes(name, a, b):
        worst = int(np.abs(a.astype(np.int32) - b.astype(np.int32)).max())
        require(worst <= Q_STEP, f"{name}: int8 codes differ by {worst} steps")
        return worst

    def close(name, a, b, **tol):
        np.testing.assert_allclose(a, b, err_msg=name, **tol)
        return float(np.abs(a - b).max())

    report = {
        "quantize_stack.q": codes("quantize_stack q", got["q"], rq),
        "quantize_stack.scales": close("quantize_stack scales", got["s"], rs,
                                       rtol=SCALE_RTOL),
        "fedavg": close("fedavg", got["agg"], ragg, atol=F32_ATOL),
        "cwmed.q": codes("cwmed quantize_out q", got["qo"], rqo),
        "cwmed.scales": close("cwmed scales", got["so"], rso,
                              rtol=SCALE_RTOL),
        "candidates": close("candidates", got["cands"], rcands,
                            atol=F32_ATOL),
    }
    log(f"# kernels vs oracles at D={D} K={K} (max abs difference): "
        + json.dumps(report))


def round_phase(*, width: int = 32, num_clients: int = 100,
                rounds: int = ROUNDS) -> None:
    from jax.flatten_util import ravel_pytree

    ds = make_dataset(num_clients)
    for label, cfg, validator in (("f32 chain", ROUND_CFG, "committee"),
                                  ("int8 chain", INT8_CFG, "committee_int8")):
        rt, _, accs = run_rounds(ds, cfg, width=width, rounds=rounds,
                                 validator=validator, label=label)
        require(accs[-1] > accs[0],
                f"{label}: test accuracy did not rise ({accs})")
    D = int(ravel_pytree(rt.global_params())[0].shape[0])
    check_kernels(D)


# ----------------------------------------------------------------------
# serve phase
# ----------------------------------------------------------------------
def serve_phase(cfg=None, *, slots: int = 4, prompt_lens=(32, 64),
                gen_lens=(16, 32), requests: int = 6) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import registry
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shardings import ShardingPolicy
    from repro.launch.steps import make_prefill_step
    from repro.models import init_model
    from repro.models.transformer import Batch
    from repro.serve import ServeEngine, make_poisson_trace

    cfg = cfg or registry.get_config("olmo-1b")
    max_len = max(prompt_lens) + max(gen_lens)
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"# serve: {cfg.name} {cfg.num_units} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {n_params} parameters")
    engine = ServeEngine(cfg, params, num_slots=slots, max_len=max_len)
    trace = make_poisson_trace(
        num_requests=requests, rate=50.0, prompt_lens=prompt_lens,
        gen_lens=gen_lens, vocab_size=cfg.vocab_size, seed=SEED,
    )
    t0 = time.perf_counter()
    engine.warmup(prompt_lens)
    log(f"# serve: warmup (compile included) {time.perf_counter() - t0:.3f} s")
    report = engine.run(trace)
    for r in report.results:
        require(len(r.tokens) == r.max_new,
                f"request {r.rid}: {len(r.tokens)} of {r.max_new} tokens")
        require(all(0 <= t < cfg.vocab_size for t in r.tokens),
                f"request {r.rid}: token id out of range")
    m = report.metrics()
    log(f"# serve: {len(report.results)} requests, {report.ticks} ticks, "
        f"{sum(len(r.tokens) for r in report.results)} tokens in "
        f"{report.wall_s:.3f} s; metrics {json.dumps(m)}")

    pol = ShardingPolicy(dp_axes=("data",), dp_sizes=(1,),
                         model_axis_size=1, fsdp=False)
    prefill = jax.jit(make_prefill_step(cfg, make_host_mesh(1, 1), pol,
                                        max_len=max_len))
    for req in trace[:2]:
        S = req.prompt_len
        batch = Batch(tokens=jnp.asarray(req.prompt, jnp.int32)[None],
                      positions=jnp.arange(S, dtype=jnp.int32)[None])
        logits = np.asarray(prefill(params, batch)[0])
        require(logits.shape == (1, 1, cfg.vocab_size),
                f"prefill logits shape {logits.shape}")
        require(np.isfinite(logits).all(), f"request {req.rid}: bad logits")
        first = int(np.argmax(logits[0, -1]))
        log(f"# serve: request {req.rid} prefill logits finite; first token "
            f"{first} (engine {report.results[req.rid].tokens[0]})")


# ----------------------------------------------------------------------
# sharded phase (--chips 4)
# ----------------------------------------------------------------------
def sharded_phase(*, chips: int = 4, width: int = 32,
                  num_clients: int = 100, rounds: int = ROUNDS) -> None:
    import numpy as np
    from jax.flatten_util import ravel_pytree

    from repro.core.blockchain import pytree_digest
    from repro.launch.mesh import make_round_mesh

    import jax

    ds = make_dataset(num_clients)
    runs = {}
    # At the TPU's default precision an f32 matmul takes one bf16 pass,
    # and its result changes with the number of clients or candidates one
    # program holds (12 here, 3 per shard there).  A model near its
    # initialisation has near-tied logits, so that flips validation
    # predictions on one chip as on four.  Full f32 passes leave only the
    # f32 summation order.
    with jax.default_matmul_precision(SHARDED_PRECISION):
        for label, mesh, validator in (
            ("one chip", None, "committee_int8"),
            (f"{chips} chips", make_round_mesh(chips),
             "committee_int8_sharded"),
        ):
            rt, scores, _ = run_rounds(
                ds, INT8_CFG, width=width, rounds=rounds, validator=validator,
                mesh=mesh, label=f"int8 chain, {label}, "
                f"{SHARDED_PRECISION} matmul precision")
            runs[label] = (rt, scores.first)
    (one, s1), (many, sn) = runs.values()

    require(s1.shape == sn.shape, f"score shapes {s1.shape} {sn.shape}")
    score_diff = float(np.abs(s1 - sn).max())
    # round 1's model block, and the int8 scales of the rows it averaged
    p1 = ravel_pytree(one.chain.model_at_round(1))[0]
    pn = ravel_pytree(many.chain.model_at_round(1))[0]
    param_diff = float(np.abs(np.asarray(p1) - np.asarray(pn)).max())
    step = max(float(np.max(np.asarray(b["scales"])))
               for b in one.chain.update_payloads_at_round(0, decode=False))
    # update blobs carry the sharded codec's extra zero tiles, so block
    # hashes may differ where the models they commit do not
    same_hashes = ([b.hash for b in one.chain.blocks]
                   == [b.hash for b in many.chain.blocks])
    same_models = all(
        pytree_digest(one.chain.model_at_round(t))
        == pytree_digest(many.chain.model_at_round(t))
        for t in range(rounds + 1))
    log("# sharded vs one chip: " + json.dumps({
        "scores_shape": list(s1.shape),
        "scores_max_abs_diff": score_diff, "scores_atol": SCORE_ATOL,
        "round1_params_max_abs_diff": param_diff,
        "round1_params_atol": PARAM_STEPS * step,
        "chain_hashes_equal": same_hashes,
        "model_digests_equal": same_models,
    }))
    require(score_diff <= SCORE_ATOL, "score matrices differ")
    require(param_diff <= PARAM_STEPS * step, "round-1 parameters differ")


# ----------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded round engine against one chip")
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops

    log(f"# compile cache: {enable_compile_cache()}")
    require(not ops._interpret(), "kernels would run in interpret mode")
    probe = jax.ShapeDtypeStruct((8, 430080), jax.numpy.float32)
    text = jax.jit(lambda x: ops.quantize_stack(x)[:2]).lower(probe) \
        .compile().as_text()
    require("tpu_custom_call" in text,
            "the int8 codec did not lower to Mosaic")
    log(f"# device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(chips=4)
    else:
        round_phase()
        serve_phase()
    log(f"# total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
