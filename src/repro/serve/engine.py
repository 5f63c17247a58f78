"""Slot-based continuous-batching engine over the compiled serving steps.

A fixed-capacity decode batch of ``num_slots`` request slots runs ONE fused
decode step per tick (``launch/steps.make_decode_step`` with logits dropped
and the position/cache buffers donated).  Admission is prefill-into-slot:
a queued request is prefilled at its exact prompt length (batch 1) and its
KV state written into the freed slot row (``models.cache.insert_slot_cache``)
— no batch barrier, so short requests never wait on long ones.  Finished
slots free at the tick boundary on which their generation budget is spent;
finish detection is count-based, so the hot loop never blocks on token
values: each tick's token vector is fetched one tick late, while the next
tick is already in flight on device.

A free slot holds position -1 on the device: the tick advances only live
slots, the attention kernel reads no block of a free slot and only the
blocks a live slot holds up to its position, and a freed slot is set back
to -1 by one small jitted update.  ``ServeReport.kv_read_share`` is the
mean share of the attention layers' allocated KV rows that a tick reads,
counted after the run from each request's prompt length and tokens.

Each phase of the loop runs inside an ``engine.<phase>`` span
(``repro.tracing``: admit, prefill, insert, tick, fetch, swap, idle); the
run's host seconds per span come back as ``ServeReport.host_s``.

The engine also watches a ``ParamSource`` (live chain or checkpoint
directory — ``repro.serve.params``) and hot-swaps the whole parameter pytree
at a tick boundary when a new round commits a model block.  In-flight
requests keep their caches and keep decoding; nothing is dropped.
"""
from __future__ import annotations

import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import ShardingPolicy
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import init_cache
from repro.models.attention import ragged_block
from repro.models.cache import attn_cache_len, insert_slot_cache
from repro.models.config import ModelConfig
from repro.models.transformer import Batch
from repro.serve.scheduler import FifoScheduler
from repro.serve.slots import Request, RequestResult, SlotTable
from repro.serve.trace import aggregate
from repro.tracing import span


# ----------------------------------------------------------------------------
# clocks
# ----------------------------------------------------------------------------


class WallClock:
    """Real time — the benchmark's clock."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def tick(self) -> None:
        pass

    def advance_to(self, t: float) -> None:
        delta = t - self.now()
        if delta > 0:
            time.sleep(min(delta, 0.002))


class VirtualClock:
    """Deterministic tick-counting clock — the test harness's clock.

    Time advances ``dt`` per decode tick and jumps to the next arrival when
    the engine idles, so admission order (and therefore every decoded token)
    is reproducible run-to-run."""

    def __init__(self, dt: float = 1.0):
        self.dt = dt
        self._t = 0.0

    def now(self) -> float:
        return self._t

    def tick(self) -> None:
        self._t += self.dt

    def advance_to(self, t: float) -> None:
        if t > self._t:
            self._t = t


# ----------------------------------------------------------------------------
# engine
# ----------------------------------------------------------------------------


@dataclass
class _Pending:
    """A launched-but-not-fetched token vector: drained one tick late."""

    tok: Any                                      # device array (rows, 1)
    # (rid, row, is_first_token, is_last_token)
    deliveries: List[Tuple[int, int, bool, bool]]
    version: int


@dataclass
class ServeReport:
    results: List[RequestResult]
    wall_s: float
    ticks: int
    occupancy: float                              # mean active-slot fraction
    swaps: List[Dict[str, Any]]
    policy: str
    # host seconds inside each ``engine.<phase>`` span over the run
    host_s: Dict[str, float] = field(default_factory=dict)
    # mean over ticks of the attention layers' KV rows read / rows allocated
    kv_read_share: float = 0.0

    def metrics(self) -> Dict[str, float]:
        out = aggregate(
            self.results, wall_s=self.wall_s, ticks=self.ticks,
            occupancy=self.occupancy, swaps=len(self.swaps),
        )
        out["kv_read_share"] = round(self.kv_read_share, 4)
        per_tick = max(1, self.ticks)
        for name, secs in sorted(self.host_s.items()):
            out[f"{name}_ms_per_tick"] = round(secs * 1e3 / per_tick, 4)
        return out

    def by_rid(self) -> Dict[int, RequestResult]:
        return {r.rid: r for r in self.results}


def kv_layers(cfg: ModelConfig, max_len: int, mesh=None) -> Counter:
    """The attention layers, counted by ``(block, rows)``: the rows per
    block in which the decode tick reads a layer's cache (0: every row,
    ``models.attention.ragged_block``) and the rows each slot holds."""
    layers: Counter = Counter()
    for spec in cfg.all_layers():
        if spec.mixer.startswith("attn"):
            sc = attn_cache_len(cfg, spec.mixer, max_len)
            layers[ragged_block(cfg, spec.mixer, sc, mesh), sc] += 1
    return layers


def kv_rows_read(layers: Counter, num_slots: int, live_pos: np.ndarray,
                 ticks: int = 1) -> Tuple[int, int]:
    """(KV rows read, KV rows allocated) over ``layers`` (``kv_layers``)
    in ``ticks`` ticks whose live slots, all ticks' together, sit at
    ``live_pos``: a ragged layer reads each live slot's blocks up to its
    position, a dense layer every row of every slot."""
    read = alloc = 0
    for (block, sc), n in layers.items():
        alloc += ticks * n * num_slots * sc
        if block:
            need = np.minimum(np.asarray(live_pos) // block + 1, sc // block)
            read += n * int(need.sum()) * block
        else:
            read += ticks * n * num_slots * sc
    return read, alloc


class ServeEngine:
    """Continuous-batching server for one decoder model."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        num_slots: int = 4,
        max_len: int = 128,
        mesh=None,
        pol: Optional[ShardingPolicy] = None,
        param_source=None,
        swap_poll_every: int = 1,
    ):
        if not cfg.is_decoder():
            raise ValueError(f"{cfg.name} is encoder-only: nothing to serve")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.source = param_source
        self.swap_poll_every = max(1, swap_poll_every)
        self.version = getattr(param_source, "version", 0) or 0
        self._mrope = cfg.rope == "mrope"

        mesh = mesh or make_host_mesh(1, 1)
        pol = pol or ShardingPolicy(
            dp_axes=("data",), dp_sizes=(1,), model_axis_size=1, fsdp=False
        )
        prefill_step = make_prefill_step(cfg, mesh, pol, max_len=max_len)
        decode_step = make_decode_step(cfg, mesh, pol, return_logits=False)

        def prefill_tok(params, batch):
            logits, cache = prefill_step(params, batch)
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return tok[:, None], cache

        # one trace per distinct prompt length (jit's shape cache)
        self._prefill = jax.jit(prefill_tok)

        mrope = self._mrope

        def tick(params, tokens, positions, cache):
            mp = (
                jnp.broadcast_to(
                    positions[None, :, None], (3, positions.shape[0], 1)
                )
                if mrope else None
            )
            next_tok, new_cache = decode_step(params, tokens, positions, cache, mp)
            # a free slot (-1) stays free
            positions = jnp.where(positions >= 0, positions + 1, -1)
            return next_tok, positions, new_cache

        # positions/cache donated: the cache is updated in place, because
        # ``models.transformer.decode_step`` carries the stacked unit cache
        # through its layer scan and scatters each layer's new rows into the
        # carry (a cache emitted as a scan output would be a second copy).
        # The token vector is NOT donated — the previous tick's tokens are
        # still held by the deferred-fetch queue.
        self._tick = jax.jit(tick, donate_argnums=(2, 3))

        def insert(cache, tokens, positions, slot_cache, first_tok, pos0, b):
            cache = insert_slot_cache(cache, slot_cache, b)
            tokens = jax.lax.dynamic_update_slice(tokens, first_tok, (b, jnp.int32(0)))
            positions = jax.lax.dynamic_update_slice(positions, pos0[None], (b,))
            return tokens, positions, cache

        self._insert = jax.jit(insert, donate_argnums=(0, 2))
        self._free = jax.jit(lambda positions, b: positions.at[b].set(-1),
                             donate_argnums=0)
        self._kv_layers = kv_layers(cfg, max_len, mesh)
        # host seconds per ``engine.<phase>`` span of the current run
        self._host_s: Dict[str, float] = {}

    # ------------------------------------------------------------------
    def _make_prompt_batch(self, prompt: np.ndarray) -> Batch:
        S = int(prompt.shape[0])
        toks = jnp.asarray(prompt, jnp.int32)[None]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (1, S))
        batch = Batch(tokens=toks, positions=pos)
        if self._mrope:
            batch = batch._replace(
                positions=jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None, None], (3, 1, S)
                ),
                embeds=jnp.zeros((1, S, self.cfg.d_model),
                                 jnp.dtype(self.cfg.dtype)),
                embed_mask=jnp.zeros((1, S), bool),
            )
        return batch

    def _fresh_state(self):
        tokens = jnp.zeros((self.num_slots, 1), jnp.int32)
        positions = jnp.full((self.num_slots,), -1, jnp.int32)
        cache = init_cache(self.cfg, self.num_slots, self.max_len,
                           jnp.dtype(self.cfg.dtype))
        return tokens, positions, cache

    def warmup(self, prompt_lens: Sequence[int]) -> None:
        """Compile every hot-path trace (per-bucket prefill, insert, tick)
        outside the timed window."""
        tokens, positions, cache = self._fresh_state()
        b = jnp.asarray(0, jnp.int32)
        for S in sorted(set(int(s) for s in prompt_lens)):
            batch = self._make_prompt_batch(np.zeros((S,), np.int32))
            tok, slot_cache = self._prefill(self.params, batch)
            tokens, positions, cache = self._insert(
                cache, tokens, positions, slot_cache, tok,
                jnp.asarray(S, jnp.int32), b,
            )
        tokens, positions, cache = self._tick(
            self.params, tokens, positions, cache
        )
        positions = self._free(positions, b)
        jax.block_until_ready(positions)

    # ------------------------------------------------------------------
    def _poll_swap(self, tick_idx: int, clock, swaps: List[dict]) -> None:
        if self.source is None:
            return
        with span("engine.swap", self._host_s):
            got = self.source.poll()
            if got is None:
                return
            ver, new_params = got
            # cast onto the serving dtype layout; structure must match,
            # which a chain model block / checkpoint of the same arch
            # guarantees
            self.params = jax.tree.map(
                lambda n, o: jnp.asarray(n, o.dtype), new_params, self.params
            )
        self.version = ver
        swaps.append({"round": int(ver), "tick": tick_idx,
                      "t": round(clock.now(), 6)})

    def _drain(self, pending: Deque[_Pending],
               results: Dict[int, RequestResult], clock,
               force: bool = False) -> None:
        """Fetch token vectors one tick late: the block on ``np.asarray``
        overlaps with the next tick already running on device."""
        while pending and (force or len(pending) > 1):
            rec = pending.popleft()
            with span("engine.fetch", self._host_s):
                toks = np.asarray(rec.tok)
            now = clock.now()
            for rid, row, first, last in rec.deliveries:
                r = results[rid]
                r.tokens.append(int(toks[row, 0]))
                if first:
                    r.first_token = now
                if last:
                    r.finished = now
                    r.version_finished = rec.version

    # ------------------------------------------------------------------
    def run(
        self,
        requests: Sequence[Request],
        *,
        policy: str = "continuous",
        clock=None,
        on_tick: Optional[Callable[[int], None]] = None,
    ) -> ServeReport:
        """Serve a trace to completion and return the per-request results.

        ``on_tick(tick_idx)`` fires at every tick boundary — the benchmark
        uses it to commit a new model block to the watched chain mid-trace.
        """
        for r in requests:
            if r.max_new < 1:
                raise ValueError(f"request {r.rid}: max_new must be >= 1")
            if r.prompt_len < 1:
                raise ValueError(f"request {r.rid}: empty prompt")
            if r.prompt_len + r.max_new - 1 > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {r.prompt_len} + gen {r.max_new}"
                    f" exceeds max_len {self.max_len}"
                )

        clock = clock or WallClock()
        sched = FifoScheduler(requests, policy=policy)
        table = SlotTable(self.num_slots)
        tokens, positions, cache = self._fresh_state()
        results: Dict[int, RequestResult] = {
            r.rid: RequestResult(rid=r.rid, prompt_len=r.prompt_len,
                                 max_new=r.max_new, arrival=r.arrival)
            for r in requests
        }
        pending: Deque[_Pending] = deque()
        swaps: List[dict] = []
        tick_idx = 0
        active_ticks = 0          # sum of active slots over all ticks
        host_s = self._host_s = {}
        t_start = time.perf_counter()

        while not (sched.exhausted and table.all_free and not pending):
            if tick_idx % self.swap_poll_every == 0:
                self._poll_swap(tick_idx, clock, swaps)

            # ---- admissions (prefill-into-slot) --------------------------
            for b, req in sched.admissions(table, clock.now()):
                with span("engine.admit", host_s):
                    res = results[req.rid]
                    res.admitted = clock.now()
                    res.version_admitted = self.version
                    batch = self._make_prompt_batch(req.prompt)
                    with span("engine.prefill", host_s):
                        tok, slot_cache = self._prefill(self.params, batch)
                    one_shot = req.max_new == 1
                    pending.append(_Pending(
                        tok=tok,
                        deliveries=[(req.rid, 0, True, one_shot)],
                        version=self.version,
                    ))
                    if not one_shot:
                        with span("engine.insert", host_s):
                            tokens, positions, cache = self._insert(
                                cache, tokens, positions, slot_cache, tok,
                                jnp.asarray(req.prompt_len, jnp.int32),
                                jnp.asarray(b, jnp.int32),
                            )
                        table.occupy(b, req.rid, req.max_new - 1)

            # ---- one fused decode tick over the whole slot batch ---------
            if table.num_active:
                rids = table.active_snapshot()
                with span("engine.tick", host_s):
                    tokens, positions, cache = self._tick(
                        self.params, tokens, positions, cache
                    )
                done_slots = table.decrement_active()
                done_set = set(done_slots)
                deliveries = [
                    (int(rids[b]), b, False, b in done_set)
                    for b in range(self.num_slots)
                    if rids[b] >= 0
                ]
                pending.append(_Pending(tok=tokens, deliveries=deliveries,
                                        version=self.version))
                for b in done_slots:
                    table.release(b)
                    positions = self._free(positions,
                                           jnp.asarray(b, jnp.int32))
                active_ticks += len(deliveries)
                tick_idx += 1
                clock.tick()
                if on_tick is not None:
                    on_tick(tick_idx)
                self._drain(pending, results, clock)
            else:
                # idle: nothing decoding — drain stragglers, jump to the
                # next arrival
                self._drain(pending, results, clock, force=True)
                na = sched.next_arrival()
                if na is not None:
                    with span("engine.idle", host_s):
                        clock.advance_to(na)

        self._drain(pending, results, clock, force=True)
        wall = time.perf_counter() - t_start
        occupancy = (active_ticks / (tick_idx * self.num_slots)
                     if tick_idx else 0.0)
        ordered = [results[r.rid] for r in sorted(requests, key=lambda q: q.rid)]
        # a request that got n tokens decoded in n - 1 ticks, from its prompt
        live_pos = [r.prompt_len + j for r in ordered
                    for j in range(len(r.tokens) - 1)]
        rows, alloc = kv_rows_read(self._kv_layers, self.num_slots, live_pos,
                                   tick_idx)
        return ServeReport(results=ordered, wall_s=wall, ticks=tick_idx,
                           occupancy=occupancy, swaps=swaps, policy=policy,
                           host_s=dict(host_s),
                           kv_read_share=rows / alloc if alloc else 0.0)
