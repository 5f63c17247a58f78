"""The main-path Pallas kernels compiled for a described TPU v5e.

Interpret mode runs every kernel on the CPU, but it cannot show what the
chip's compiler refuses (block shapes that break the TPU tiling rule, fast
memory over budget).  These tests lower the ops-level programs with the
kernels in compiled mode at the width of the paper's model (femnist-cnn at
width 32: D = 430080 after padding, K = 8 update rows) for a v5e chip that
is described, not attached, and check that each program holds a Mosaic
kernel (``tpu_custom_call``).  Nothing runs, so results are not checked
here; the kernel tests in interpret mode and the benchmark (``bench/``, whose
round cell checks the int8 round against a float32 reference) on the chip
do that.

The topology is described inside a fixture only: describing it loads the
TPU library, which one process at a time may hold.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.tiling import BLOCK_D

K = 8
D = 430080                       # femnist-cnn width 32, padded to BLOCK_D
NBLK = D // BLOCK_D
# an HLO instruction: its result shape and its opcode
_OP = re.compile(r"\s*(?:ROOT )?%\S+ = (\S+) ([\w-]+)\(")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops layer picks interpret mode from the CPU backend this process
    runs on; the chip takes the compiled branch."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_quantize_stack_compiles(compiled_kernels, one_chip):
    _compile(lambda x: ops.quantize_stack(x)[:2],
             _sds((K, D), jnp.float32, one_chip))


def test_quantize_dequantize_compile(compiled_kernels, one_chip):
    _compile(lambda x: ops.quantize(x)[:2], _sds((D,), jnp.float32, one_chip))
    _compile(lambda q, s: ops.dequantize(q, s, D),
             _sds((D,), jnp.int8, one_chip),
             _sds((NBLK,), jnp.float32, one_chip))


@pytest.mark.parametrize("method,quantize_out", [
    ("fedavg", False),
    ("cwmed", True),
    ("trimmed_mean", False),
])
def test_fused_agg_compiles(compiled_kernels, one_chip, method, quantize_out):
    def agg(q, s, w):
        out = ops.aggregate_quantized(q, s, D, method=method, weights=w,
                                      quantize_out=quantize_out)
        return out[:2] if quantize_out else out

    _compile(agg, _sds((K, D), jnp.int8, one_chip),
             _sds((K, NBLK), jnp.float32, one_chip),
             _sds((K,), jnp.float32, one_chip))


def test_fused_candidates_compiles(compiled_kernels, one_chip):
    _compile(lambda base, q, s: ops.candidates_from_quantized(base, q, s, D),
             _sds((D,), jnp.float32, one_chip),
             _sds((K, D), jnp.int8, one_chip),
             _sds((K, NBLK), jnp.float32, one_chip))


@pytest.mark.parametrize("method", ["fedavg", "cwmed", "trimmed_mean"])
def test_f32_aggregators_compile(compiled_kernels, one_chip, method):
    _compile(lambda x, w: ops.aggregate(x, method, weights=w),
             _sds((K, D), jnp.float32, one_chip),
             _sds((K,), jnp.float32, one_chip))


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np

    return jax.sharding.Mesh(np.array(topo.devices[:4]), ("data",))


def test_sharded_quantize_stack_compiles(compiled_kernels, mesh4):
    fn = ops.make_quantize_stack_sharded(mesh4)
    text = fn.lower(_sds((K, D), jnp.float32, NamedSharding(mesh4, P()))
                    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method", ["fedavg", "cwmed"])
def test_sharded_fused_agg_compiles(compiled_kernels, mesh4, method):
    fn = ops.make_aggregate_quantized_sharded(mesh4, method=method)
    cols = NamedSharding(mesh4, P(None, "data"))
    dpad = ops.padded_dim_sharded(D, 4)      # every shard tile-aligned
    text = fn.lower(_sds((K, dpad), jnp.int8, cols),
                    _sds((K, dpad // BLOCK_D), jnp.float32, cols),
                    _sds((K,), jnp.float32, NamedSharding(mesh4, P()))
                    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_int8_scorer_compiles(compiled_kernels, one_chip):
    """The committee's score-from-int8 program: P = 12 candidates of the
    width-32 CNN against Q = 8 members' validation batches of 64."""
    from jax.flatten_util import ravel_pytree

    from repro.fl import femnist_adapter
    from repro.fl.client import make_score_from_int8_fn

    adapter = femnist_adapter(width=32)
    params = adapter.init(jax.random.PRNGKey(0))
    flat, unravel = ravel_pytree(params)
    score = make_score_from_int8_fn(adapter, unravel)
    place = lambda x: _sds(x.shape, x.dtype, one_chip)  # noqa: E731
    text = score.lower(
        jax.tree.map(place, params),
        _sds((12, flat.shape[0]), jnp.float32, one_chip),
        _sds((8, 64, 28, 28, 1), jnp.float32, one_chip),
        _sds((8, 64), jnp.int32, one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("U,B,Sc,Kv,H", [
    (16, 16, 1024, 16, 16),      # olmo-1b: 16 slots x 1024, one query per head
    (2, 16, 4352, 1, 20),        # jamba2-3b: one KV head for 20 queries
], ids=["olmo-1b", "jamba2-3b"])
def test_decode_attention_compiles(compiled_kernels, one_chip, U, B, Sc, Kv,
                                   H):
    """The serving tick's ragged attention over the decode scan's whole
    stacked slot cache: the kernel reads the stacks where they lie, so the
    program holds no copy of them."""
    Dh = 128

    def attend(q, k, v, pos, q_pos, unit):
        return ops.decode_attention(q, k, v, pos, q_pos, unit, block=256)

    text = _compile(attend, _sds((B, H, Dh), jnp.float32, one_chip),
                    _sds((U, B, Sc, Kv, Dh), jnp.float32, one_chip),
                    _sds((U, B, Sc, Kv, Dh), jnp.float32, one_chip),
                    _sds((U, B, Sc), jnp.int32, one_chip),
                    _sds((B,), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip))
    # no op but the operands themselves makes a stack-sized or unit-sized
    # array beside the kernel
    whole = re.compile(rf"\[(\d+,)?{B},{Sc},{Kv},{Dh}\]")
    ops_made = [m.groups() for m in map(_OP.match, text.splitlines()) if m]
    assert ops_made
    assert not [(shape, op) for shape, op in ops_made if whole.search(shape)
                and op not in ("parameter", "custom-call")]
