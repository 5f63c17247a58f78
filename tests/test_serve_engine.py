"""Continuous-batching serve engine: oracle parity, scheduling behaviour,
and chain hot-swap correctness.

The load-bearing pins:
  * every request served by the slot engine decodes the SAME token ids as a
    single-request (batch-1) oracle run — in-flight batching must not change
    results;
  * a mid-trace hot swap completes without dropping in-flight requests, and
    requests served entirely under one params version stay oracle-exact for
    that version.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import ShardingPolicy
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import init_cache, init_model
from repro.models.cache import insert_slot_cache
from repro.models.transformer import Batch
from repro.serve import (
    ChainParamSource,
    CheckpointParamSource,
    FifoScheduler,
    Request,
    ServeEngine,
    SlotTable,
    VirtualClock,
    make_poisson_trace,
)

MAX_LEN = 48


@pytest.fixture(scope="module")
def cfg():
    return registry.get_config(
        "olmo-1b", d_model=64, num_units=2, num_heads=2, num_kv_heads=2,
        d_ff=128, vocab_size=512,
    )


@pytest.fixture(scope="module")
def params(cfg):
    return init_model(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def host_steps(cfg):
    mesh = make_host_mesh(1, 1)
    pol = ShardingPolicy(dp_axes=("data",), dp_sizes=(1,),
                         model_axis_size=1, fsdp=False)
    prefill = jax.jit(make_prefill_step(cfg, mesh, pol, max_len=MAX_LEN))
    decode = jax.jit(make_decode_step(cfg, mesh, pol, return_logits=False))
    return prefill, decode


def oracle_tokens(prefill, decode, params, prompt, max_new):
    """Batch-1 greedy generation: the single-request reference."""
    S = len(prompt)
    batch = Batch(
        tokens=jnp.asarray(prompt, jnp.int32)[None],
        positions=jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (1, S)),
    )
    logits, cache = prefill(params, batch)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    out = [int(tok[0, 0])]
    pos = jnp.asarray([S], jnp.int32)
    for _ in range(max_new - 1):
        tok, cache = decode(params, tok, pos, cache, None)
        out.append(int(tok[0, 0]))
        pos = pos + 1
    return out


def mixed_trace(cfg, *, seed=1):
    rng = np.random.default_rng(seed)
    shapes = [(8, 5), (16, 12), (8, 1), (12, 3), (16, 8), (8, 6), (12, 10)]
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
            max_new=g,
            arrival=float(i),
        )
        for i, (s, g) in enumerate(shapes)
    ]


# ----------------------------------------------------------------------------
# building blocks
# ----------------------------------------------------------------------------


def test_insert_slot_cache_writes_one_row(cfg):
    big = init_cache(cfg, 3, MAX_LEN, jnp.float32)
    small = init_cache(cfg, 1, MAX_LEN, jnp.float32)
    small = jax.tree.map(lambda x: jnp.full_like(x, 7), small)
    out = insert_slot_cache(big, small, jnp.asarray(1, jnp.int32))
    # unit leaves: stacked (num_units, B, ...) — batch axis 1
    for leaf_big, leaf_out in zip(jax.tree.leaves(big["units"]),
                                  jax.tree.leaves(out["units"])):
        np.testing.assert_array_equal(
            np.asarray(leaf_out[:, 1]), np.full_like(leaf_big[:, 1], 7))
        np.testing.assert_array_equal(
            np.asarray(leaf_out[:, 0]), np.asarray(leaf_big[:, 0]))
        np.testing.assert_array_equal(
            np.asarray(leaf_out[:, 2]), np.asarray(leaf_big[:, 2]))
    # tail leaves: plain (B, ...) — batch axis 0
    for leaf_big, leaf_out in zip(jax.tree.leaves(big["tail"]),
                                  jax.tree.leaves(out["tail"])):
        np.testing.assert_array_equal(
            np.asarray(leaf_out[1]), np.full_like(leaf_big[1], 7))
        np.testing.assert_array_equal(
            np.asarray(leaf_out[0]), np.asarray(leaf_big[0]))


def test_decode_step_logits_optin(cfg, params):
    mesh = make_host_mesh(1, 1)
    pol = ShardingPolicy(dp_axes=("data",), dp_sizes=(1,),
                         model_axis_size=1, fsdp=False)
    with_logits = jax.jit(make_decode_step(cfg, mesh, pol))
    no_logits = jax.jit(make_decode_step(cfg, mesh, pol, return_logits=False))
    cache = init_cache(cfg, 2, MAX_LEN, jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray([4, 9], jnp.int32)
    t3, logits, _ = with_logits(params, toks, pos, cache, None)
    out = no_logits(params, toks, pos, cache, None)
    assert len(out) == 2, "logits must be dropped when opted out"
    t2, _ = out
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t3))
    assert logits.shape == (2, 1, cfg.vocab_size)


def test_scheduler_static_barrier():
    reqs = [Request(rid=i, prompt=np.zeros((4,), np.int32), max_new=2,
                    arrival=0.0) for i in range(4)]
    table = SlotTable(2)
    sched = FifoScheduler(reqs, policy="static")
    first = sched.admissions(table, 0.0)
    assert [b for b, _ in first] == [0, 1]
    for b, r in first:
        table.occupy(b, r.rid, r.max_new)
    table.release(0)
    # one slot free, one busy: static admits nothing until the batch drains
    assert sched.admissions(table, 0.0) == []
    table.release(1)
    assert len(sched.admissions(table, 0.0)) == 2


def test_scheduler_continuous_fills_any_free_slot():
    reqs = [Request(rid=i, prompt=np.zeros((4,), np.int32), max_new=2,
                    arrival=float(i)) for i in range(3)]
    table = SlotTable(2)
    sched = FifoScheduler(reqs, policy="continuous")
    got = sched.admissions(table, 0.0)
    assert len(got) == 1                      # only rid 0 has arrived
    table.occupy(got[0][0], 0, 2)
    got = sched.admissions(table, 5.0)        # rids 1,2 arrived; 1 slot free
    assert len(got) == 1 and got[0][1].rid == 1
    assert sched.queued == 1


def test_poisson_trace_shapes():
    trace = make_poisson_trace(num_requests=32, rate=10.0,
                               prompt_lens=(4, 8), gen_lens=(2, 6),
                               vocab_size=100, seed=3)
    assert len(trace) == 32
    arrivals = [r.arrival for r in trace]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0
    assert all(r.prompt_len in (4, 8) and r.max_new in (2, 6) for r in trace)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 100 for r in trace)


def test_engine_rejects_oversized_request(cfg, params):
    eng = ServeEngine(cfg, params, num_slots=2, max_len=16)
    bad = [Request(rid=0, prompt=np.zeros((12,), np.int32), max_new=8)]
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.run(bad, clock=VirtualClock())


# ----------------------------------------------------------------------------
# oracle parity
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("policy,num_slots", [
    pytest.param("continuous", 3, id="continuous"),
    pytest.param("static", 3, id="static"),
    # more slots than requests in flight: each decodes beside free slots
    pytest.param("continuous", 8, id="continuous-free-slots"),
])
def test_engine_matches_single_request_oracle(cfg, params, host_steps, policy,
                                              num_slots):
    prefill, decode = host_steps
    trace = mixed_trace(cfg)
    eng = ServeEngine(cfg, params, num_slots=num_slots, max_len=MAX_LEN)
    rep = eng.run(trace, policy=policy, clock=VirtualClock())
    assert rep.policy == policy
    for res, req in zip(rep.results, trace):
        assert len(res.tokens) == req.max_new
        want = oracle_tokens(prefill, decode, params, req.prompt, req.max_new)
        assert res.tokens == want, (policy, res.rid)
    m = rep.metrics()
    assert m["requests"] == len(trace)
    assert m["generated_tokens"] == sum(r.max_new for r in trace)
    assert 0.0 < rep.occupancy <= 1.0


def test_freed_slot_stays_free_until_insert(cfg, params):
    """A free slot holds position -1: ticks neither advance it nor write
    its cache rows; a freed slot returns to -1, and an insert revives it
    at its prompt length."""
    eng = ServeEngine(cfg, params, num_slots=3, max_len=MAX_LEN)
    tokens, positions, cache = eng._fresh_state()
    np.testing.assert_array_equal(np.asarray(positions), [-1, -1, -1])
    prompt = np.arange(1, 9, dtype=np.int32)

    def insert(tokens, positions, cache, b):
        tok, slot_cache = eng._prefill(params, eng._make_prompt_batch(prompt))
        return eng._insert(cache, tokens, positions, slot_cache, tok,
                           jnp.asarray(len(prompt), jnp.int32),
                           jnp.asarray(b, jnp.int32))

    def ticks(n, tokens, positions, cache):
        for _ in range(n):
            tokens, positions, cache = eng._tick(params, tokens, positions,
                                                 cache)
        return tokens, positions, cache

    tokens, positions, cache = insert(tokens, positions, cache, 1)
    tokens, positions, cache = ticks(3, tokens, positions, cache)
    np.testing.assert_array_equal(np.asarray(positions), [-1, 11, -1])
    for layer in cache["units"]:
        # free slots: no row written; slot 1: its prompt and three ticks
        assert (np.asarray(layer["pos"][:, [0, 2]]) == -1).all()
        assert (np.asarray(layer["pos"][:, 1, :11]) == np.arange(11)).all()

    positions = eng._free(positions, jnp.asarray(1, jnp.int32))
    tokens, positions, cache = ticks(2, tokens, positions, cache)
    np.testing.assert_array_equal(np.asarray(positions), [-1, -1, -1])
    for layer in cache["units"]:
        # the freed slot's rows stay as its request left them
        assert (np.asarray(layer["pos"][:, 1, :11]) == np.arange(11)).all()
        assert (np.asarray(layer["pos"][:, 1, 11:]) == -1).all()

    tokens, positions, cache = insert(tokens, positions, cache, 1)
    np.testing.assert_array_equal(np.asarray(positions), [-1, 8, -1])
    tokens, positions, cache = ticks(1, tokens, positions, cache)
    np.testing.assert_array_equal(np.asarray(positions), [-1, 9, -1])


def test_kv_read_share_counts_block_rounded_rows(cfg, params):
    """Two requests of known lengths on a 384-row cache, read in blocks of
    128 rows: each tick reads, per live slot, its blocks up to its
    position, over the rows the slots hold."""
    max_len, block, slots = 384, 128, 2
    rng = np.random.default_rng(5)
    trace = [
        Request(rid=0, prompt=rng.integers(0, 64, (120,)).astype(np.int32),
                max_new=20),
        Request(rid=1, prompt=rng.integers(0, 64, (10,)).astype(np.int32),
                max_new=5),
    ]
    eng = ServeEngine(cfg, params, num_slots=slots, max_len=max_len)
    rep = eng.run(trace, clock=VirtualClock())
    assert rep.ticks == 19

    def rows(p):
        return (p // block + 1) * block

    shares = [(rows(120 + t) + (rows(10 + t) if t < 4 else 0))
              / (slots * max_len) for t in range(rep.ticks)]
    assert rep.kv_read_share == pytest.approx(np.mean(shares))
    assert rep.metrics()["kv_read_share"] == round(rep.kv_read_share, 4)


def test_continuous_frees_slots_static_stalls(cfg, params):
    """One long request pins a slot; short requests keep arriving.  The
    continuous engine serves them through the freed slot while the long one
    decodes; the static barrier parks them until the whole batch drains."""
    rng = np.random.default_rng(0)

    def mk(rid, gen, arrival):
        return Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
            max_new=gen, arrival=arrival,
        )

    trace = [mk(0, 30, 0.0), mk(1, 4, 0.0), mk(2, 4, 1.0), mk(3, 4, 2.0)]
    eng = ServeEngine(cfg, params, num_slots=2, max_len=MAX_LEN)
    cont = eng.run(trace, policy="continuous", clock=VirtualClock()).by_rid()
    stat = eng.run(trace, policy="static", clock=VirtualClock()).by_rid()
    # static: rid 1 finished early but its slot stays barred — rids 2/3 are
    # only admitted once the 30-token request drains the batch
    assert stat[2].admitted > stat[1].finished
    # continuous: rid 2 rides the slot rid 1 freed, long before that
    assert cont[2].admitted < stat[2].admitted
    assert cont[3].first_token < stat[3].first_token
    assert cont[3].finished < stat[3].finished


# ----------------------------------------------------------------------------
# hot swap
# ----------------------------------------------------------------------------


def test_chain_hot_swap_keeps_untouched_slots_oracle_exact(
        cfg, params, host_steps):
    from repro.core.blockchain import Chain

    prefill, decode = host_steps
    params1 = init_model(jax.random.PRNGKey(9), cfg)
    chain = Chain(k_updates_per_round=1)
    chain.append_model(params, 0)

    rng = np.random.default_rng(4)

    def mk(rid, gen, arrival):
        return Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
            max_new=gen, arrival=arrival,
        )

    # rid 0 finishes before the swap; rid 1 spans it; rid 2 starts after
    trace = [mk(0, 3, 0.0), mk(1, 24, 0.0), mk(2, 5, 10.0)]
    swap_tick = 6
    committed = []

    def commit(tick):
        if tick == swap_tick and not committed:
            chain.append_update(jax.tree.map(np.zeros_like, params),
                                uploader=0, score=1.0)
            chain.append_model(params1, 1)
            committed.append(tick)

    eng = ServeEngine(cfg, params, num_slots=2, max_len=MAX_LEN,
                      param_source=ChainParamSource(chain))
    rep = eng.run(trace, policy="continuous", clock=VirtualClock(),
                  on_tick=commit)
    assert len(rep.swaps) == 1 and rep.swaps[0]["round"] == 1
    by = rep.by_rid()

    # nothing dropped or truncated across the swap
    for req in trace:
        assert len(by[req.rid].tokens) == req.max_new

    # pre-swap request: bit-identical to the params-v0 oracle
    assert by[0].version_admitted == 0 and by[0].version_finished == 0
    assert not by[0].spans_swap
    assert by[0].tokens == oracle_tokens(
        prefill, decode, params, trace[0].prompt, 3)

    # post-swap request: bit-identical to the params-v1 oracle
    assert by[2].version_admitted == 1 and by[2].version_finished == 1
    assert by[2].tokens == oracle_tokens(
        prefill, decode, params1, trace[2].prompt, 5)

    # the spanning request crossed versions, met its budget, and its
    # pre-swap prefix is v0-oracle-exact — the swap changes params only,
    # never the in-flight KV state
    assert by[1].spans_swap
    v0 = oracle_tokens(prefill, decode, params, trace[1].prompt, 24)
    assert by[1].tokens[:4] == v0[:4]


def test_checkpoint_param_source_roundtrip(cfg, params, tmp_path):
    from repro.checkpoint import save_pytree
    from repro.kernels.ops import Int8UpdateCodec
    from repro.serve.params import checkpoint_name

    src = CheckpointParamSource(str(tmp_path), start_round=0)
    assert src.poll() is None

    params1 = init_model(jax.random.PRNGKey(3), cfg)
    save_pytree(str(tmp_path / checkpoint_name(1)), params1)
    ver, got = src.poll()
    assert ver == 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert src.poll() is None                 # same round: no re-swap

    # int8-codec chain blob snapshot: decoded through the codec
    codec = Int8UpdateCodec(params)
    blob = codec.encode(params1)
    save_pytree(str(tmp_path / checkpoint_name(2)), blob)
    src2 = CheckpointParamSource(str(tmp_path), codec=codec, start_round=1)
    ver, got = src2.poll()
    assert ver == 2
    want = codec.decode(blob)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
