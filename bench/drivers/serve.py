"""Driver of the serving cells: ``ServeEngine.run`` over an open-loop trace.

Set-up makes the weights on the device from the seed, builds the engine
and warms up every shape the mix uses (one prefill per prompt bucket, the
slot insert and the decode tick).  The window serves every request that
arrives in ``seconds`` and drains them.  After the window a sample of the
finished requests, drawn from the seed and holding the one with the most
served tokens, goes through the plain reference once each, prompt and
served tokens together, and the widest gap by which a served token's
logit lies below the reference's best is compared with the cell's limit.
So are the keys and values the slot cache holds at the window's end, for
the last request of every slot, against the reference's.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

SIZE_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_units",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "layer_norm_eps": "norm_eps", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings"}


def program_config(config: Dict[str, Any]):
    """The program's model configuration, held to the sizes the benchmark's
    file states."""
    from repro.configs import registry

    pcfg = registry.get_config(config["program"])
    pcfg = pcfg.replace(**config.get("program_overrides", {}))
    for ours, theirs in SIZE_KEYS.items():
        if getattr(pcfg, theirs) != config[ours]:
            raise ValueError(f"{config['name']}: the program has {theirs}="
                             f"{getattr(pcfg, theirs)!r}, the configuration "
                             f"states {ours}={config[ours]!r}")
    return pcfg


def sample_requests(results, n: int, seed: int) -> List[Any]:
    """The finished request with the most tokens and n - 1 others."""
    done = [r for r in results if r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r.rid != longest.rid]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(model, weights, config, samples, max_len: int,
                   control_precision=None, control_dtype=None
                   ) -> List[np.ndarray]:
    """Per sampled request, at each position whose next token was served,
    the gap of the served token below the reference's best logit (float32,
    highest matmul precision).  With ``control_precision`` (and
    ``control_dtype``), at the same positions of the same prompt and served
    tokens, the gap of the token that the reference at that precision (in
    that dtype) puts first."""
    import jax
    import jax.numpy as jnp

    out_len = max(len(r.tokens) for r in samples)

    def rows(w, seq, start, dtype=jnp.float32):
        return model.forward(w, seq, config, dtype=dtype)[
            start + jnp.arange(out_len)]

    def gap_of(hi, toks, mask):
        got = jnp.take_along_axis(hi, toks[:, None], axis=-1)[:, 0]
        return jnp.where(mask, hi.max(axis=-1) - got, 0.0)

    logits = jax.jit(rows)
    top = jax.jit(lambda w, seq, start: jnp.argmax(
        rows(w, seq, start, control_dtype or jnp.float32), -1))
    gap_j = jax.jit(gap_of)
    result = []
    for r in samples:
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:-1], np.int32)])
        padded = np.zeros(max_len, np.int32)
        padded[: len(seq)] = seq
        start = np.int32(r.prompt_len - 1)
        mask = np.arange(out_len) < len(r.tokens)
        if control_precision is not None:
            with jax.default_matmul_precision(control_precision):
                toks = top(weights, padded, start)
        else:
            toks = np.zeros(out_len, np.int32)
            toks[: len(r.tokens)] = r.tokens
        with jax.default_matmul_precision("highest"):
            g = gap_j(logits(weights, padded, start), toks, mask)
        result.append(np.asarray(g)[: len(r.tokens)])
    return result


def last_in_slot(results, slot_of: Dict[int, int]) -> Dict[int, Any]:
    """Per slot, the last request admitted to it: at the window's end the
    slot's cache rows below its length are that request's."""
    held = {}
    for r in results:
        b = slot_of.get(r.rid)
        if b is not None and (b not in held or r.admitted > held[b].admitted):
            held[b] = r
    return held


def kv_gaps(model, weights, config, held: Dict[int, Any], max_len: int,
            cache=None, control_precision=None, control_dtype=None
            ) -> Dict[int, float]:
    """Per slot, over the rows its last request wrote (its prompt and
    served tokens but the last), the worst layer's relative distance
    |k - k_ref| / |k_ref| of the keys, or of the values, from the
    reference's (float32, highest matmul precision): of the program's
    ``cache`` at the window's end or, with ``control_precision`` (and
    ``control_dtype``), of the reference's at that precision."""
    import jax
    import jax.numpy as jnp

    def kv(w, seq, dtype=jnp.float32):
        return model.forward(w, seq, config, dtype=dtype, return_kv=True)[1:]

    def gap(k, v, k_ref, v_ref, rows):
        m = rows[None, :, None, None]

        def rel(a, b):
            num = jnp.sum(jnp.where(m, a - b, 0.0) ** 2, axis=(1, 2, 3))
            den = jnp.sum(jnp.where(m, b, 0.0) ** 2, axis=(1, 2, 3))
            return jnp.sqrt(num / den).max()

        return jnp.maximum(rel(k, k_ref), rel(v, v_ref))

    ref = jax.jit(kv)
    low = jax.jit(lambda w, seq: kv(w, seq, control_dtype or jnp.float32))
    gap_j = jax.jit(gap)
    if cache is not None:
        unit = cache["units"][0]
    out = {}
    for b, r in sorted(held.items()):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:-1], np.int32)])
        padded = np.zeros(max_len, np.int32)
        padded[: len(seq)] = seq
        rows = np.arange(max_len) < len(seq)
        if control_precision is not None:
            with jax.default_matmul_precision(control_precision):
                k, v = low(weights, padded)
        else:
            k, v = unit["k"][:, b], unit["v"][:, b]
            # a slot left idle keeps ticking and, past max_len, wraps onto
            # its first rows: compare only rows still holding their position
            rows &= np.asarray(unit["pos"][0, b]) == np.arange(max_len)
        with jax.default_matmul_precision("highest"):
            k_ref, v_ref = ref(weights, padded)
            out[b] = float(gap_j(k, v, k_ref, v_ref, rows))
    return out


def run(cell: Dict[str, Any], config: Dict[str, Any], traffic: Dict[str, Any],
        *, seed: int, seconds: float, trace: bool, devices, t0: float,
        patch=None) -> Dict[str, Any]:
    import jax

    from bench.harness import (CompileCounter, derive_seeds, device_info,
                               load_module)
    from bench.spans import spanned
    from bench.trace import Tracer
    from repro.serve import ServeEngine
    from repro.serve.engine import WallClock

    model = load_module("configs", config.get("reference", config["name"]))
    gen = load_module("traffic", traffic["generator"])
    s_w, s_req, s_sample = derive_seeds(seed, 3)
    pcfg = program_config(config)
    weights = model.make_weights(jax.random.PRNGKey(s_w), config)
    srv = config["serving"]
    max_len = int(srv["max_len"])
    eng = ServeEngine(pcfg, weights, num_slots=int(srv["num_slots"]),
                      max_len=max_len)
    final = {}                     # the slot cache the last call returned

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
    requests = gen.make_requests(traffic, s_req, seconds, config["vocab_size"])
    prompts = {r.rid: r.prompt for r in requests}
    eng.warmup(sorted(set(traffic["prompt_buckets"])))
    final.clear()                  # the warm-up's cache is not the window's

    counter = CompileCounter()
    tracer = Tracer(trace)
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
    samples = sample_requests(results, int(cell.get("check_requests", 8)),
                              s_sample)
    held = last_in_slot(results, slot_of)
    for r in samples + list(held.values()):
        r.prompt = prompts[r.rid]
    t_ref = time.perf_counter()
    gaps = reference_gaps(model, weights, config, samples, max_len)
    widest = max(float(g.max()) for g in gaps) if gaps else float("inf")
    kv = (kv_gaps(model, weights, config, held, max_len, final.pop("cache"))
          if held else {})
    kv_diff = max(kv.values(), default=float("inf"))
    t_ref = time.perf_counter() - t_ref
    numbers = {"logit_gap": widest, "kv_diff": kv_diff}
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    reqs = [{"arrival": r.arrival, "admitted": r.admitted,
             "first_token": r.first_token, "finished": r.finished,
             "tokens": len(r.tokens), "max_new": r.max_new,
             "prompt_len": r.prompt_len} for r in results]
    record = {
        "kind": "serve", "seconds": seconds, "t0": t0, "t_open": t_open,
        "t_close": t_close, "setup_s": t_open - t0,
        "window_s": t_close - t_open, "requests": reqs,
        "deliveries": deliveries,
        "ticks": report.ticks, "occupancy": report.occupancy,
        "num_slots": int(srv["num_slots"]), "max_len": max_len,
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
                      "slots checked": len(kv),
                      "reference seconds": round(t_ref, 3)}}
