"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination with 512 placeholder host devices, print memory/cost analysis,
and dump the roofline record for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""
# The first two lines MUST run before any other import touches jax: jax locks
# the device count on first initialization.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.launch import hlo_stats
from repro.launch.mesh import dp_axes as mesh_dp_axes, make_production_mesh
from repro.launch.shardings import (
    ShardingPolicy,
    batch_pspecs,
    cache_pspecs,
    named,
    param_pspecs,
)
from repro.launch.steps import (
    TrainState,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.models import init_cache, init_model
from repro.models.config import ModelConfig
from repro.models.moe import virtual_factor
from repro.models.transformer import Batch
from repro.optim import adamw, linear_warmup_cosine

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def shape_applicable(cfg: ModelConfig, shape: str) -> Optional[str]:
    """None if runnable, else the skip reason (DESIGN.md §5)."""
    if shape in ("decode_32k", "long_500k") and not cfg.is_decoder():
        return "encoder-only: no decode step"
    if shape == "long_500k" and not cfg.is_subquadratic():
        return "pure full attention: 500k decode cache unbounded"
    return None


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def make_input_specs(cfg: ModelConfig, shape: str, *, val_rows: int = 0):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    info = SHAPES[shape]
    B, S = info["batch"], info["seq"]
    dt = jnp.dtype(cfg.dtype)
    if info["kind"] in ("train", "prefill"):
        if cfg.frontend == "audio":
            batch = Batch(
                tokens=None,
                embeds=sds((B, S, cfg.d_model), dt),
                embed_mask=sds((B, S), jnp.bool_),
                positions=sds((B, S), jnp.int32),
                targets=sds((B, S), jnp.int32),
                loss_mask=sds((B, S), jnp.float32),
            )
        elif cfg.frontend == "vision":
            batch = Batch(
                tokens=sds((B, S), jnp.int32),
                embeds=sds((B, S, cfg.d_model), dt),
                embed_mask=sds((B, S), jnp.bool_),
                positions=sds((3, B, S), jnp.int32),
                targets=sds((B, S), jnp.int32),
                loss_mask=sds((B, S), jnp.float32),
            )
        else:
            batch = Batch(
                tokens=sds((B, S), jnp.int32),
                embeds=None,
                embed_mask=None,
                positions=sds((B, S), jnp.int32),
                targets=sds((B, S), jnp.int32),
                loss_mask=sds((B, S), jnp.float32),
            )
        return batch
    # decode
    tokens = sds((B, 1), jnp.int32)
    position = sds((B,), jnp.int32)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, B, S, jnp.dtype(cfg.dtype))
    )
    mrope = sds((3, B, 1), jnp.int32) if cfg.rope == "mrope" else None
    return tokens, position, cache, mrope


def make_val_batch_specs(cfg: ModelConfig, rows: int, seq: int = 1024):
    dt = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio":
        return Batch(
            tokens=None,
            embeds=sds((rows, seq, cfg.d_model), dt),
            embed_mask=sds((rows, seq), jnp.bool_),
            positions=sds((rows, seq), jnp.int32),
            targets=sds((rows, seq), jnp.int32),
            loss_mask=sds((rows, seq), jnp.float32),
        )
    if cfg.frontend == "vision":
        return Batch(
            tokens=sds((rows, seq), jnp.int32),
            embeds=sds((rows, seq, cfg.d_model), dt),
            embed_mask=sds((rows, seq), jnp.bool_),
            positions=sds((3, rows, seq), jnp.int32),
            targets=sds((rows, seq), jnp.int32),
            loss_mask=sds((rows, seq), jnp.float32),
        )
    return Batch(
        tokens=sds((rows, seq), jnp.int32),
        embeds=None,
        embed_mask=None,
        positions=sds((rows, seq), jnp.int32),
        targets=sds((rows, seq), jnp.int32),
        loss_mask=sds((rows, seq), jnp.float32),
    )


def dryrun_one(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    mode: str = "bflc",
    policy_overrides: Optional[dict] = None,
    verbose: bool = True,
    save: bool = True,
    tag: str = "baseline",
    remat="unit",
    microbatches: int = 1,
) -> Dict:
    cfg = registry.get_config(
        arch, dtype="bfloat16",
        remat="layer" if remat == "layer" else True,
    )
    reason = shape_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode, "tag": tag,
    }
    if reason:
        rec["skipped"] = reason
        if verbose:
            print(f"[skip] {arch} x {shape}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    dp = mesh_dp_axes(mesh)
    pol = ShardingPolicy(
        dp_axes=dp,
        dp_sizes=tuple(mesh.shape[a] for a in dp),
        model_axis_size=mesh.shape["model"],
        **(policy_overrides or {}),
    )
    info = SHAPES[shape]
    virtual_r = (
        virtual_factor(cfg, mesh.shape["model"]) if cfg.num_experts else 1
    )

    params_shape = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, virtual_r=virtual_r)
    )
    pspecs = param_pspecs(cfg, params_shape, pol)
    param_shardings = named(mesh, pspecs)

    t0 = time.perf_counter()
    try:
        if info["kind"] == "train":
            moment_dtype = (
                jnp.bfloat16 if registry.param_count(cfg) > 5e10 else None
            )
            opt = adamw(
                linear_warmup_cosine(3e-4, 100, 10_000),
                moment_dtype=moment_dtype, weight_decay=0.1,
            )
            opt_state_shape = jax.eval_shape(opt.init, params_shape)
            opt_pspecs = {"m": pspecs, "v": pspecs}
            dp_total = 1
            for a in dp:
                dp_total *= mesh.shape[a]
            step_fn = make_train_step(
                cfg, opt, mesh, pol, mode=mode,
                num_cohorts=dp_total, committee_size=dp_total,
                num_microbatches=microbatches,
            )
            batch = make_input_specs(cfg, shape)
            val_batch = (
                make_val_batch_specs(cfg, dp_total) if mode == "bflc" else None
            )
            bspec = batch_pspecs(cfg, pol, batch_sharded=True)
            state_shardings = TrainState(
                params=param_shardings,
                opt_state=named(mesh, opt_pspecs),
                step=NamedSharding(mesh, P()),
            )
            state_shape = TrainState(
                params=params_shape,
                opt_state=opt_state_shape,
                step=sds((), jnp.int32),
            )
            in_shardings = (
                state_shardings,
                named(mesh, bspec),
                named(mesh, batch_pspecs(cfg, pol, batch_sharded=True))
                if val_batch is not None else None,
            )
            out_shardings = (state_shardings, NamedSharding(mesh, P()))
            args = (state_shape, batch) + (
                (val_batch,) if val_batch is not None else (None,)
            )
            jitted = jax.jit(
                step_fn,
                in_shardings=(
                    in_shardings[0], in_shardings[1], in_shardings[2]
                ),
                out_shardings=out_shardings,
                donate_argnums=(0,),   # alias old->new TrainState buffers
            )
            lowered = jitted.lower(*args)
        elif info["kind"] == "prefill":
            step_fn = make_prefill_step(cfg, mesh, pol, max_len=info["seq"])
            batch = make_input_specs(cfg, shape)
            bspec = batch_pspecs(cfg, pol, batch_sharded=True)
            cache_shape = jax.eval_shape(
                lambda p, b: step_fn(p, b)[1], params_shape, batch
            ) if cfg.is_decoder() else None
            out_cache_shardings = (
                named(mesh, cache_pspecs(cfg, cache_shape, pol,
                                         batch_sharded=True))
                if cache_shape is not None else None
            )
            if cfg.is_decoder():
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(param_shardings, named(mesh, bspec)),
                    out_shardings=(
                        NamedSharding(mesh, P()),
                        out_cache_shardings,
                    ),
                )
            else:
                # encoder: "prefill" = full-sequence encode (logits only)
                from repro.models import forward as fwd

                def encode(params, b):
                    return fwd(params, cfg, b)[0]

                jitted = jax.jit(
                    encode,
                    in_shardings=(param_shardings, named(mesh, bspec)),
                )
                step_fn = encode
            lowered = jitted.lower(params_shape, batch)
        else:  # decode
            B = info["batch"]
            batch_sharded = B > 1
            step_fn = make_decode_step(
                cfg, mesh, pol, batch_sharded=batch_sharded
            )
            tokens, position, cache, mrope = make_input_specs(cfg, shape)
            cspecs = cache_pspecs(cfg, cache, pol, batch_sharded=batch_sharded)
            dp_or_none = dp if batch_sharded else None
            tok_sh = NamedSharding(mesh, P(dp_or_none, None))
            pos_sh = NamedSharding(mesh, P(dp_or_none))
            mrope_sh = (
                NamedSharding(mesh, P(None, dp_or_none, None))
                if mrope is not None else None
            )
            jitted = jax.jit(
                step_fn,
                in_shardings=(
                    param_shardings, tok_sh, pos_sh, named(mesh, cspecs),
                    mrope_sh,
                ),
                out_shardings=(
                    tok_sh, NamedSharding(mesh, P(dp_or_none, None, "model")),
                    named(mesh, cspecs),
                ),
            )
            lowered = jitted.lower(params_shape, tokens, position, cache, mrope)

        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = hlo_stats.collective_stats(hlo)
        comp_stats = hlo_stats.hlo_compute_stats(hlo)
        # trip-count-aware matmul FLOPs/bytes (cost_analysis does not
        # multiply while-loop bodies — see hlo_stats.hlo_compute_stats)
        flops = float(comp_stats["dot_flops"])
        bytes_acc = max(
            float(cost.get("bytes accessed", 0.0)),
            float(comp_stats["dot_bytes"]),
        )
        terms = hlo_stats.roofline_terms(
            flops=flops, bytes_accessed=bytes_acc,
            collective_bytes=float(coll.total_bytes), chips=1,
            device_kind="TPU v5 lite",  # the production mesh's chip
        )  # all values are per-device post-SPMD; chips=1 keeps units right
        rec.update({
            "chips": chips,
            "compile_s": round(compile_s, 1),
            "flops_per_device": flops,
            "flops_cost_analysis": float(cost.get("flops", 0.0)),
            "bytes_per_device": bytes_acc,
            "dot_bytes_per_device": int(comp_stats["dot_bytes"]),
            "collective_bytes_per_device": int(coll.total_bytes),
            "collective_breakdown": coll.bytes_by_kind,
            "collective_counts": coll.count_by_kind,
            "peak_memory_per_device": getattr(
                mem, "temp_size_in_bytes", None
            ),
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "roofline": terms,
            "params": registry.param_count(cfg),
            "active_params": registry.active_param_count(cfg),
        })
        if verbose:
            print(
                f"[ok] {arch} x {shape} x {rec['mesh']} ({tag}): "
                f"compile {compile_s:.0f}s, "
                f"{flops/1e12:.2f} TF/dev, {bytes_acc/1e9:.2f} GB/dev, "
                f"coll {coll.total_bytes/1e9:.3f} GB/dev, "
                f"dominant={terms['dominant']}"
            )
            print(f"     memory_analysis: {mem}")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[FAIL] {arch} x {shape} x {rec['mesh']}: {rec['error']}")

    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fname = f"{arch}_{shape}_{rec['mesh'].replace('x','-')}_{tag}.json"
        with open(os.path.join(OUT_DIR, fname), "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(registry.ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="bflc", choices=["bflc", "standard"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--moe-2d", action="store_true")
    ap.add_argument("--remat", default="unit", choices=["unit", "layer"])
    ap.add_argument("--act-shard-d", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    pairs = []
    if args.all:
        for a in registry.ARCH_IDS:
            for s in SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    overrides = {}
    if args.no_fsdp:
        overrides["fsdp"] = False
    if args.seq_parallel:
        overrides["seq_parallel_acts"] = True
    if args.moe_2d:
        overrides["moe_tp_over_dp"] = True
    if args.act_shard_d:
        overrides["act_shard_d"] = True
    overrides = overrides or None
    failures = 0
    for mp in meshes:
        for a, s in pairs:
            rec = dryrun_one(
                a, s, multi_pod=mp, mode=args.mode,
                policy_overrides=overrides, tag=args.tag,
                remat=args.remat, microbatches=args.microbatches,
            )
            failures += 1 if "error" in rec else 0
    print(f"\ndone; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
