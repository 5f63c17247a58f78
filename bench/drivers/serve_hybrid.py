"""Driver of the serving cells of a hybrid model, Mamba-1 and attention
layers in a periodic stack: ``ServeEngine.run`` over an open-loop trace.

Set-up and window are ``serve.py``'s: the weights on the device from the
seed, every shape the mix uses warmed up, every request that arrives in
``seconds`` served and drained.  The check differs in what the slot cache
holds.  Its sample (the request with the most tokens to serve and n - 1
others, as ``serve.sample_requests`` draws them) is drawn before the window
from the requests themselves, so that the tick that produces a sampled
request's last token can leave a copy of its slot's recurrent state: that
tick has consumed the prompt and every served token but the last.  After the
window, the reference (float32, highest matmul precision) goes once over
each sampled request and each slot's last request, and three numbers are
compared with the cell's limits:

* ``logit_gap``: the widest gap by which a served token's logit lies below
  the reference's best (``serve.reference_gaps``);
* ``kv_diff``: per slot, the worst attention layer's relative distance of
  the keys, or of the values, the cache holds at the window's end for the
  slot's last request, over the rows whose position is still theirs;
* ``state_diff``: per sampled request, the worst Mamba layer's relative
  distance of the SSM state, or of the convolution state, that the timed
  tick left, from the reference's after the same tokens.
"""
from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import numpy as np

from bench.harness import load_module

serve = load_module("drivers", "serve")

# the published config's keys and the program's, held equal
SIZE_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
             "mamba_d_state": "mamba_d_state", "mamba_d_conv": "mamba_d_conv",
             "mamba_expand": "mamba_expand", "mamba_dt_rank": "resolved_dt_rank"}


def program_config(config: Dict[str, Any]):
    """The program's model configuration, held to the sizes and the layer
    pattern that the benchmark's file states."""
    from repro.configs import registry

    model = load_module("configs", config.get("reference", config["name"]))
    pcfg = registry.get_config(config["program"])
    over = dict(config.get("program_overrides", {}))
    if "unit" in over:             # a cut-down stack: its period's pattern
        from repro.models.config import periodic_unit

        over["unit"] = periodic_unit(**over["unit"])
    pcfg = pcfg.replace(**over)

    def differ(what, ours, theirs):
        raise ValueError(f"{config['name']}: the program has {what}={ours!r}, "
                         f"the configuration states {theirs!r}")

    for theirs, ours in SIZE_KEYS.items():
        if getattr(pcfg, ours) != config[theirs]:
            differ(ours, getattr(pcfg, ours), config[theirs])
    if pcfg.resolved_head_dim * pcfg.num_heads != pcfg.d_model:
        differ("head_dim", pcfg.resolved_head_dim, "hidden / heads")
    if pcfg.rope != "none":
        differ("rope", pcfg.rope, "no positional encoding")
    layers = [s.mixer for s in pcfg.all_layers()]
    stated = model.kinds(config) * (config["num_hidden_layers"]
                                    // model.period(config))
    if layers != stated:
        differ("layers", layers, stated)
    moe = [s.mlp for s in pcfg.all_layers() if s.mlp != "dense"]
    if config["num_experts"] == 1 and moe:
        differ("MLPs", moe, "all dense (num_experts 1)")
    return pcfg


def plan_sample(requests, n: int, seed: int) -> List[int]:
    """The ids ``serve.sample_requests`` draws when every request is served
    in full."""
    full = [SimpleNamespace(rid=r.rid, tokens=[0] * r.max_new)
            for r in requests]
    return [r.rid for r in serve.sample_requests(full, n, seed)]


def _seq(r, max_len: int):
    """A served request's prompt and served tokens but the last, padded."""
    seq = np.concatenate([np.asarray(r.prompt, np.int32),
                          np.asarray(r.tokens[:-1], np.int32)])
    padded = np.zeros(max_len, np.int32)
    padded[: len(seq)] = seq
    return padded, len(seq)


def _rel(a, b, axes):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum((a - b) ** 2, axis=axes)
                    / jnp.sum(b ** 2, axis=axes)).max()


def state_kv_gaps(model, weights, config, reqs: Dict[int, Any], max_len: int,
                  kv: Optional[Dict[int, Any]] = None,
                  state: Optional[Dict[int, Any]] = None,
                  control_precision=None, control_dtype=None):
    """Per request of ``reqs`` (id -> result), the worst attention layer's
    relative distance of the keys or values (``kv``: id -> (k, v, pos) the
    cache held) and the worst Mamba layer's of the convolution or SSM state
    (``state``: id -> (conv, ssm) the tick left), each from the reference's
    (float32, highest matmul precision) after the same tokens.  With
    ``control_precision`` (and ``control_dtype``) the reference at that
    precision (in that dtype) stands in for the program.  Returns
    ({id: kv gap}, {id: state gap})."""
    import jax
    import jax.numpy as jnp

    def run(w, seq, n, dtype=jnp.float32):
        return model.forward(w, seq, config, dtype=dtype, return_kv=True,
                             return_state=True, length=n)[1:]

    ref = jax.jit(run)
    low = jax.jit(lambda w, seq, n: run(w, seq, n,
                                        control_dtype or jnp.float32))

    def kv_gap(k, v, k_ref, v_ref, rows):
        m = rows[None, :, None, None]
        return jnp.maximum(
            _rel(jnp.where(m, k, 0.0), jnp.where(m, k_ref, 0.0), (1, 2, 3)),
            _rel(jnp.where(m, v, 0.0), jnp.where(m, v_ref, 0.0), (1, 2, 3)))

    def state_gap(conv, ssm, conv_ref, ssm_ref):
        return jnp.maximum(_rel(conv, conv_ref, (1, 2)),
                           _rel(ssm, ssm_ref, (1, 2)))

    kv_j, state_j = jax.jit(kv_gap), jax.jit(state_gap)
    kv_out, state_out = {}, {}
    for rid, r in sorted(reqs.items()):
        padded, n = _seq(r, max_len)
        with jax.default_matmul_precision("highest"):
            k_ref, v_ref, conv_ref, ssm_ref = ref(weights, padded, n)
        rows = np.arange(max_len) < n
        if control_precision is not None:
            with jax.default_matmul_precision(control_precision):
                k, v, conv, ssm = low(weights, padded, n)
            kv_src, state_src = {rid: (k, v, None)}, {rid: (conv, ssm)}
        else:
            kv_src, state_src = kv or {}, state or {}
        if rid in kv_src:
            k, v, pos = kv_src[rid]
            if pos is not None:
                # a slot left idle keeps ticking and, past max_len, wraps
                # onto its first rows: compare rows still holding their
                # position
                rows &= np.asarray(pos) == np.arange(max_len)
            kv_out[rid] = float(kv_j(k, v, k_ref, v_ref, rows))
        if rid in state_src:
            state_out[rid] = float(state_j(*state_src[rid], conv_ref,
                                           ssm_ref))
    return kv_out, state_out


def run(cell: Dict[str, Any], config: Dict[str, Any], traffic: Dict[str, Any],
        *, seed: int, seconds: float, trace: bool, devices, t0: float,
        patch=None) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from bench.harness import CompileCounter, derive_seeds, device_info
    from bench.scope_trace import ScopeTracer
    from bench.spans import spanned
    from repro.models import init_cache
    from repro.serve import ServeEngine
    from repro.serve.engine import WallClock

    model = load_module("configs", config.get("reference", config["name"]))
    gen = load_module("traffic", traffic["generator"])
    s_w, s_req, s_sample = derive_seeds(seed, 3)
    pcfg = program_config(config)
    weights = model.make_weights(jax.random.PRNGKey(s_w), config)
    srv = config["serving"]
    max_len, num_slots = int(srv["max_len"]), int(srv["num_slots"])
    eng = ServeEngine(pcfg, weights, num_slots=num_slots, max_len=max_len)
    unit = pcfg.unit
    attn_at = [i for i, s in enumerate(unit) if s.mixer.startswith("attn")]
    mamba_at = [i for i, s in enumerate(unit) if s.mixer == "mamba"]

    def by_depth(units, at, key, b):
        """Slot b's leaf ``key`` of the layers at ``at`` of each period,
        in order of depth."""
        a = jnp.stack([jax.lax.dynamic_index_in_dim(units[i][key], b, 1,
                                                    keepdims=False)
                       for i in at], axis=1)
        return a.reshape((-1,) + a.shape[2:])

    shapes = jax.eval_shape(lambda: init_cache(pcfg, num_slots, max_len,
                                               jnp.dtype(pcfg.dtype)))
    b_shape = jax.ShapeDtypeStruct((), jnp.int32)
    # compiled in set-up: one slot's recurrent state, (conv, ssm)
    take_state = jax.jit(lambda units, b: (
        by_depth(units, mamba_at, "conv", b),
        by_depth(units, mamba_at, "ssm", b))).lower(
            shapes["units"], b_shape).compile()

    requests = gen.make_requests(traffic, s_req, seconds, config["vocab_size"])
    prompts = {r.rid: r.prompt for r in requests}
    sample_rids = plan_sample(requests, int(cell.get("check_requests", 8)),
                              s_sample)
    final = {}                     # the slot cache the last call returned
    states = {}                    # sampled id -> the state its last tick left

    def keeping(fn):
        def call(*args):
            out = fn(*args)
            final["cache"] = out[2]
            return out

        return call

    eng._prefill = spanned("serve.prefill", eng._prefill)
    eng._tick = keeping(spanned("serve.tick", eng._tick))
    eng._insert = keeping(spanned("serve.insert", eng._insert))
    deliveries = []                # (clock time, tokens delivered then)
    slot_of = {}                   # request id -> the slot it decoded in
    drain = eng._drain

    def counted_drain(pending, results, clock, force=False):
        if not force and pending:  # right after a tick: its record is last
            for rid, row, first, last in pending[-1].deliveries:
                if last and not first and rid in sample_rids:
                    states[rid] = take_state(final["cache"]["units"],
                                             jnp.int32(row))
        for p in pending:
            for rid, row, first, _ in p.deliveries:
                if not first:      # a tick's delivery: the row is the slot
                    slot_of[rid] = row
        before = sum(len(p.deliveries) for p in pending)
        drain(pending, results, clock, force)
        after = sum(len(p.deliveries) for p in pending)
        if before > after:
            deliveries.append((clock.now(), before - after))

    eng._drain = counted_drain
    if patch is not None:          # a planted fault (tests only)
        patch(eng)
    eng.warmup(sorted(set(traffic["prompt_buckets"])))
    final.clear()                  # the warm-up's cache is not the window's

    counter = CompileCounter()
    tracer = ScopeTracer(trace, module="jit_prefill_tok",
                         state_shape=f"[1,{pcfg.mamba_d_inner},"
                                     f"{pcfg.mamba_d_state}]")
    with tracer:
        counter.active = True
        clock = WallClock()
        t_open = time.perf_counter()
        tracer.open()
        report = eng.run(requests, clock=clock)
        t_close = time.perf_counter()
        tracer.close()
        counter.active = False
    device = device_info(devices)
    del eng

    results = report.results
    failed = sum(1 for r in results if len(r.tokens) != r.max_new)
    by_rid = {r.rid: r for r in results}
    samples = [by_rid[i] for i in sample_rids if by_rid[i].tokens]
    held = serve.last_in_slot(results, slot_of)
    for r in samples + list(held.values()):
        r.prompt = prompts[r.rid]
    cache = final.pop("cache")["units"] if held else None
    kv = {r.rid: (by_depth(cache, attn_at, "k", b),
                  by_depth(cache, attn_at, "v", b),
                  cache[attn_at[0]]["pos"][0, b])
          for b, r in held.items()}
    del cache
    t_ref = time.perf_counter()
    gaps = serve.reference_gaps(model, weights, config, samples, max_len)
    widest = max(float(g.max()) for g in gaps) if gaps else float("inf")
    reqs = {r.rid: r for r in samples + list(held.values())}
    kv_gap, state_gap = state_kv_gaps(model, weights, config, reqs, max_len,
                                      kv=kv, state=states)
    t_ref = time.perf_counter() - t_ref
    kv_diff = max(kv_gap.values(), default=float("inf"))
    # a sampled request whose last tick left no state fails the check
    state_diff = max((state_gap.get(i, float("inf")) for i in sample_rids),
                     default=float("inf"))
    numbers = {"logit_gap": widest, "kv_diff": kv_diff,
               "state_diff": state_diff}
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    reqs_rec = [{"arrival": r.arrival, "admitted": r.admitted,
                 "first_token": r.first_token, "finished": r.finished,
                 "tokens": len(r.tokens), "max_new": r.max_new,
                 "prompt_len": r.prompt_len} for r in results]
    record = {
        "kind": "serve", "seconds": seconds, "t0": t0, "t_open": t_open,
        "t_close": t_close, "setup_s": t_open - t0,
        "window_s": t_close - t_open, "requests": reqs_rec,
        "deliveries": deliveries,
        "ticks": report.ticks, "occupancy": report.occupancy,
        "num_slots": num_slots, "max_len": max_len,
        "rate": traffic["rate"], "config": config, "trace": tracer.summary,
        "chips": cell["chips"], "device": device,
        "compiles_in_window": counter.count,
        "flops": {"prefill": model.prefill_flops, "decode": model.decode_flops,
                  "weight_bytes": model.weight_bytes(config),
                  "kv_bytes_per_position": model.kv_bytes_per_position(config)},
    }
    return {"record": record, "checks": checks, "attempted": len(results),
            "failed": failed, "numbers": numbers,
            "samples": samples, "held": held, "weights": weights,
            "model": model, "config": config,
            "notes": {"compilations in window": counter.count,
                      "offered rate (requests/s)": traffic["rate"],
                      "requests": len(results),
                      "served tokens checked": int(sum(len(g) for g in gaps)),
                      "slots checked": len(kv_gap),
                      "states checked": len(state_gap),
                      "prompt tokens": sum(r.prompt_len for r in results),
                      "trace kept (s of the window)": (
                          tracer.summary["truncated"]["kept_s"]
                          if tracer.summary and "truncated" in tracer.summary
                          else None),
                      "reference seconds": round(t_ref, 3)}}
