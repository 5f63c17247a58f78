"""End-to-end training driver.

Two entry modes:

* ``--driver fl``   — the paper's pipeline: BFLC over federated clients
  (synthetic FEMNIST-like data, CNN global model), with Basic-FL / CwMed /
  stand-alone comparisons.  This is the faithful-reproduction driver.
* ``--driver lm``   — the production pipeline scaled to this container: a
  ~100M-parameter decoder trained for a few hundred steps on synthetic
  Markov-chain data with the same sharded train_step the dry-run compiles
  (host mesh), in either ``standard`` or ``bflc`` (committee-weighted) mode.

Examples:
  PYTHONPATH=src python -m repro.launch.train --driver lm --steps 200
  PYTHONPATH=src python -m repro.launch.train --driver fl --rounds 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def lm_100m_config(vocab: int = 8192):
    from repro.models.config import ModelConfig, dense_unit

    return ModelConfig(
        name="repro-100m",
        arch_type="dense",
        d_model=768,
        vocab_size=vocab,
        unit=dense_unit(1),
        num_units=12,
        num_heads=12,
        num_kv_heads=4,
        d_ff=3072,
        remat=False,
    )


def run_lm(args):
    from repro.data.lm_synthetic import MarkovLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shardings import (
        ShardingPolicy, batch_pspecs, named, param_pspecs,
    )
    from repro.launch.steps import TrainState, make_train_step
    from repro.models import init_model
    from repro.models.transformer import Batch
    from repro.optim import adamw, linear_warmup_cosine

    cfg = lm_100m_config(vocab=getattr(args, 'vocab', 8192))
    if args.small:
        cfg = cfg.replace(num_units=4, d_model=256, num_heads=8,
                          num_kv_heads=4, d_ff=1024)
    mesh = make_host_mesh(1, len(jax.devices()) if args.use_all_devices else 1)
    pol = ShardingPolicy(
        dp_axes=("data",), dp_sizes=(mesh.shape["data"],),
        model_axis_size=mesh.shape["model"], fsdp=False,
    )
    opt = adamw(linear_warmup_cosine(args.lr, 20, args.steps))
    params = init_model(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params, mesh {dict(mesh.shape)}")

    step_fn = make_train_step(
        cfg, opt, mesh, pol, mode=args.mode,
        num_cohorts=args.cohorts, committee_size=args.committee,
    )
    jstep = jax.jit(step_fn)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    lm = MarkovLM(cfg.vocab_size, seed=1)
    rng = np.random.default_rng(0)
    print(f"chain entropy (loss floor): {lm.entropy():.3f} nats; "
          f"ln(V) = {np.log(cfg.vocab_size):.3f}")

    def make_batch(batch, seq):
        toks, tgts = lm.batch(rng, batch, seq)
        B, S = toks.shape
        return Batch(
            tokens=jnp.asarray(toks),
            positions=jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)),
            targets=jnp.asarray(tgts),
            loss_mask=jnp.ones((B, S), jnp.float32),
        )

    t0 = time.perf_counter()
    for step in range(args.steps):
        batch = make_batch(args.batch, args.seq)
        val = make_batch(max(args.committee, 1), args.seq) \
            if args.mode == "bflc" else None
        state, metrics = jstep(state, batch, val)
        if (step + 1) % args.log_every == 0 or step == 0:
            print(f"step {step+1:4d}  loss {float(metrics['loss']):.4f}  "
                  f"({(time.perf_counter()-t0)/(step+1):.2f}s/step)")
    if args.ckpt:
        from repro.checkpoint import save_pytree
        save_pytree(args.ckpt, state.params)
        print("saved", args.ckpt)
    return float(metrics["loss"])


def run_fl(args):
    from repro.api import build_runtime
    from repro.data import make_femnist_like
    from repro.fl import femnist_adapter

    ds = make_femnist_like(
        num_clients=args.clients, mean_samples=80, test_size=1000, seed=1
    )
    adapter = femnist_adapter(width=16)
    rt = build_runtime(adapter, ds, dict(
        active_proportion=args.active, k_updates=args.k_updates,
        local_steps=args.local_steps, malicious_fraction=args.malicious,
        seed=args.seed,
    ))
    logs = rt.run(args.rounds, eval_every=args.log_every)
    for lg in logs:
        if lg.test_accuracy is not None:
            print(f"round {lg.round:3d}  acc {lg.test_accuracy:.4f}  "
                  f"packed_malicious {lg.packed_malicious}")
    assert rt.chain.verify(), "chain integrity violated"
    print(f"chain height {rt.chain.height}, verified OK")
    return logs[-1].test_accuracy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--driver", choices=["lm", "fl"], default="lm")
    # lm
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", choices=["standard", "bflc"], default="standard")
    ap.add_argument("--cohorts", type=int, default=4)
    ap.add_argument("--committee", type=int, default=4)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--use-all-devices", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    # fl
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--active", type=float, default=0.2)
    ap.add_argument("--k-updates", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=20)
    ap.add_argument("--malicious", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.driver == "lm":
        run_lm(args)
    else:
        run_fl(args)


if __name__ == "__main__":
    main()
