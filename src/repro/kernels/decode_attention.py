"""Pallas TPU kernel: one query token per slot attending over a ragged
slot cache (the serving engine's decode tick).

The slot cache holds ``Sc`` rows per slot, but a live slot at position
``q_pos[b]`` needs only rows ``0..q_pos[b]``, and a free slot
(``q_pos[b] < 0``) none.  The kernel walks a list of steps, one per block
of ``block`` rows that some live slot needs (each live slot's blocks in
order), in one loop whose trip count is the number of live steps: a free
slot or a row past a slot's last block costs nothing.  The K, V and
position stacks are the decode scan's whole carry ``(U, B, Sc, ...)``; they
stay where they lie, and each step copies its block of the layer ``unit``
(a scalar-prefetch operand) into fast memory while the step before it
computes, so no unit is sliced out of the stack before the call.

Within a block it masks as ``models.attention._pair_mask`` does
(``pos >= 0`` and ``pos <= q_pos``) and accumulates an online softmax in
float32.  Products are VPU multiplies, exact in float32, reduced over the
head dimension.  Rows past ``q_pos``'s block are never read: for a
full-attention cache, where row ``r`` holds position ``r`` or -1, the mask
would drop them all.  A free slot's output row is zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def step_tables(q_pos: jnp.ndarray, num_blocks: int, block: int):
    """The kernel's steps: ``(slots, blocks, n_live)``, each table of length
    ``B * num_blocks``.  Steps ``0..n_live-1`` walk every live slot's blocks
    ``0..q_pos // block`` in slot order; later entries are never read."""
    B = q_pos.shape[0]
    need = jnp.where(q_pos >= 0,
                     jnp.minimum(q_pos // block + 1, num_blocks), 0)
    ends = jnp.cumsum(need)
    n_live = ends[-1]
    step = jnp.arange(B * num_blocks, dtype=jnp.int32)
    # one fused comparison, not a search loop inside the decode scan
    slots = jnp.minimum(jnp.searchsorted(ends, step, side="right",
                                         method="compare_all"), B - 1)
    blocks = step - (ends - need)[slots]
    return slots.astype(jnp.int32), blocks.astype(jnp.int32), n_live


def _kernel(meta_ref, qpos_ref, slot_ref, blk_ref,
            q_ref, k_hbm, v_hbm, pos_hbm, o_ref,
            k_buf, v_buf, pos_buf, sem, m_sc, l_sc, acc_sc,
            *, block: int, num_blocks: int):
    # q_ref (B, n, R, Dh): n head groups of R query rows; o_ref likewise.
    # k_buf and v_buf hold two steps' (block, Kv, Dh) rows, pos_buf their
    # rows' (B, block) positions, every slot's (the cache's tiling puts the
    # slots on the sublanes): one step's copy runs while the other computes
    unit, n_live = meta_ref[0], meta_ref[1]
    groups = q_ref.shape[1]

    def copies(t, buf):
        b = slot_ref[t]
        rows = pl.ds(pl.multiple_of(blk_ref[t] * block, block), block)
        return (
            pltpu.make_async_copy(k_hbm.at[unit, b, rows], k_buf.at[buf],
                                  sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[unit, b, rows], v_buf.at[buf],
                                  sem.at[1, buf]),
            pltpu.make_async_copy(pos_hbm.at[unit, :, rows],
                                  pos_buf.at[buf], sem.at[2, buf]),
        )

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_live > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def step(t, carry):
        buf = t % 2

        @pl.when(t + 1 < n_live)
        def _next():
            for c in copies(t + 1, 1 - buf):
                c.start()

        for c in copies(t, buf):
            c.wait()
        b, j = slot_ref[t], blk_ref[t]
        qp = qpos_ref[b]

        @pl.when(j == 0)
        def _init():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        pos = pos_buf[buf, pl.ds(b, 1), :].T[:, :, None]   # (block, 1, 1)
        ok = (pos >= 0) & (pos <= qp)
        k_blk, v_blk = k_buf.at[buf], v_buf.at[buf]
        for g in range(groups):
            # one query row per KV head (G == 1): the heads share a block;
            # else one KV head's G query rows
            heads = slice(None) if groups == 1 else slice(g, g + 1)
            k = k_blk[:, heads, :].astype(jnp.float32)      # (block, R|1, Dh)
            v = v_blk[:, heads, :].astype(jnp.float32)
            q = q_ref[b, g]                                 # (R, Dh)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)   # (block, R, 1)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_sc[g]                                # (R, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=0))
            p = jnp.where(ok, jnp.exp(s - m_new[None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[g] = alpha * l_sc[g] + p.sum(axis=0)
            acc_sc[g] = alpha * acc_sc[g] + jnp.sum(p * v, axis=0)
            m_sc[g] = m_new

        @pl.when(j == jnp.minimum(qp // block, num_blocks - 1))
        def _finish():
            for g in range(groups):
                o_ref[b, g] = acc_sc[g] / l_sc[g]

        return carry

    jax.lax.fori_loop(0, n_live, step, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ragged_decode_attention_kernel(q, k, v, pos, q_pos, unit, *,
                                   block: int, interpret: bool):
    """q: (B, H, Dh) float32, already scaled; k, v: (U, B, Sc, Kv, Dh);
    pos: (U, B, Sc) int32 (-1 = empty row); q_pos: (B,) int32, -1 for a
    free slot; unit: int32 scalar, the layer's index in the stack.
    Returns (B, H, Dh) float32."""
    B, H, Dh = q.shape
    Sc, Kv = k.shape[2], k.shape[3]
    G = H // Kv
    num_blocks = Sc // block
    assert Sc % block == 0, (Sc, block)
    # head groups on the sublanes: all Kv heads at once when each has one
    # query row, else one KV head's G query rows at a time
    groups, rows = (1, Kv) if G == 1 else (Kv, G)
    qg = q.reshape(B, groups, rows, Dh)
    slots, blocks, n_live = step_tables(q_pos, num_blocks, block)
    meta = jnp.stack([jnp.asarray(unit, jnp.int32), n_live.astype(jnp.int32)])

    def whole(i, *_):
        return (0, 0, 0, 0)

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[pl.BlockSpec((B, groups, rows, Dh), whole),
                  in_place, in_place, in_place],
        out_specs=pl.BlockSpec((B, groups, rows, Dh), whole),
        scratch_shapes=[
            pltpu.VMEM((2, block, Kv, Dh), k.dtype),
            pltpu.VMEM((2, block, Kv, Dh), v.dtype),
            pltpu.VMEM((2, B, block), jnp.int32),
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.VMEM((groups, rows, 1), jnp.float32),
            pltpu.VMEM((groups, rows, 1), jnp.float32),
            pltpu.VMEM((groups, rows, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, num_blocks=num_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, groups, rows, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_decode_attention",
    )(meta, q_pos.astype(jnp.int32), slots, blocks, qg, k, v, pos)
    return out.reshape(B, H, Dh)
