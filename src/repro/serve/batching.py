"""Continuous-batching serve engine: one compiled decode step, churning
requests expressed entirely as per-slot *data*.

The paper's technique is a per-step, per-row vocab-sized categorical draw
— the decode inner loop of an LLM serving stack.  This module grows the
single-step factories of :mod:`repro.serve.engine` into a request
lifecycle around that draw, holding one invariant above all others: the
decode step is traced and compiled **exactly once**, and nothing a user
can submit — prompt length, token budget, temperature, top-k/p, min-p,
seed, arrival order, queue churn — changes its shape.  The analogue of
WarpLDA/EZLDA's "fix the hot kernel, restructure the scheduling around
it", applied to serving:

* **Fixed decode batch.**  ``max_slots`` rows, always.  A request is a
  *slot assignment*; EOS / length-exhausted slots are released and
  refilled from the bounded waiting queue between steps (FCFS,
  :mod:`repro.serve.scheduler`), their KV rows reset in place by the
  insert step.
* **Per-slot positions.**  Every slot decodes at its own sequence length
  — ``cache_pos`` is a (B,) traced vector, threaded down through
  ``lm_decode`` / ``gqa_attend`` / ``mla_attend_decode`` (per-row RoPE
  angles, per-row prefix masks, each slot's new K/V row written at its
  own position after the layer scan, into the donated cache), so
  sequences of wildly different lengths share one step.
* **Per-slot sampling params as traced leaves.**  temperature / top-k /
  top-p / min-p ride in as (B,) / (B, 3) float operands; truncation is
  the butterfly-native per-row threshold (``repro.sampling.transforms``),
  so a heterogeneous batch (each request its own nucleus) is served by
  the same executable as a homogeneous one.
* **Per-slot counter-RNG streams.**  The uniform drawing request r's t-th
  token is ``threefry(seed_r, t)`` (``repro.kernels.rng``) — a pure
  function of the *request*, not the slot, the batch, or the step count.
  Slot recycling therefore cannot perturb any live stream, dead slots
  draw from their own stale streams into discarded outputs, and a
  request's tokens are bit-identical to a one-at-a-time run with the same
  seed (the recycling invariant ``tests/test_serve_engine`` pins).
* **Prefill/decode interleaving.**  Prompts prefill one request at a
  time into pow2-bucketed lengths (a handful of traces, counted
  separately), at most ``prefill_chunk`` per decode step so admission
  never starves the running batch.
* **Sharded decode composes.**  ``mesh=`` row-shards the draw through
  the same shard_map'd per-shard build+draw the PR 4 sampler uses; the
  per-slot uniforms shard with their rows, so tokens stay bit-identical
  for any device count.

Zero-retrace is *measured*, not asserted by construction:
:meth:`ContinuousBatchingEngine.compile_stats` exposes the decode step's
jit cache size and ``sampling.plan_stats()``, and the churn test +
``benchmarks/serve_bench.py`` gate them.
"""

from __future__ import annotations

import asyncio
import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs, sampling
from repro.kernels import rng as _rng
from repro.models import attention as _attn
from repro.models.model import Model
from repro.models.params import init_params
from repro.sampling import distribution as _dist
from repro.sampling import sharded as _sharded
from repro.sampling import transforms as _tr
from repro.serve.request import FinishReason, Request, RequestState
from repro.serve.scheduler import QueueFullError, Scheduler

__all__ = ["ContinuousBatchingEngine", "QueueFullError"]

# kpm block of a request that does not truncate: top_k=0, top_p=1, min_p=0
_KPM_OFF = np.array([0.0, 1.0, 0.0], np.float32)


def _bucket(n: int) -> int:
    """Smallest power of two >= n (prefill length buckets: bounded trace
    count, log2(max_len) distinct prefill shapes)."""
    return 1 << max(0, int(n - 1).bit_length())


def _seed_pair(seed):
    """A request's (2,) counter-RNG seed pair from its integer seed (a
    program of its own, ``jit__seed_pair``, apart from the prefill)."""
    return _rng.fold(_rng.seed_from_key(jax.random.PRNGKey(seed)), _rng.TAG_U)


class ContinuousBatchingEngine:
    """Asyncio serve engine over a fixed, slot-recycled decode batch.

    Synchronous core (``submit_nowait`` / ``run``) for tests and batch
    jobs; asyncio surface (``start`` / ``submit`` / ``drain`` / ``stop``)
    for open-loop serving (``benchmarks/serve_bench.py``).
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        max_slots: Optional[int] = None,
        max_len: Optional[int] = None,
        max_waiting: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        temperature: float = 1.0,
        eos_id: Optional[int] = None,
        mesh=None,
    ):
        cfg = model.cfg
        if cfg.encoder_layers > 0 or cfg.frontend_len > 0 or cfg.meta_tokens > 0:
            raise ValueError(
                "continuous batching serves plain decoder-only families; "
                f"config {cfg.name!r} has encoder/frontend/meta-token "
                "prefixes whose slot layout is not implemented"
            )
        serve = cfg.serve_spec
        self.model = model
        self.params = params
        self.max_slots = int(max_slots or serve.max_slots)
        self.max_len = int(max_len or serve.max_len)
        self.prefill_chunk = (
            serve.prefill_chunk if prefill_chunk is None else prefill_chunk
        )
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.mesh = mesh
        self.scheduler = Scheduler(
            self.max_slots,
            serve.max_waiting if max_waiting is None else max_waiting,
        )

        B, V = self.max_slots, cfg.padded_vocab
        self._plan, self._local_plan = self._resolve_plans(B, V)

        # the decode cache: (L, B, S, ...) leaves, zero-initialized once;
        # slot rows are reset in place on every admit.  It holds K/V in
        # the params' dtype, as prefill and ``generate`` produce them, so
        # the layer scan's carry keeps one dtype
        cache_dtype = jax.tree.leaves(params)[0].dtype
        self._caches = init_params(
            jax.random.PRNGKey(0), model.cache_specs(B, self.max_len),
            cache_dtype,
        )
        self._empty_prefix = init_params(
            jax.random.PRNGKey(0), model.cache_specs(1, 1), cache_dtype
        )

        # per-slot host state, device-fed each step (fixed shapes)
        self._token = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._seeds = np.zeros((B, 2), np.uint32)
        self._draw_idx = np.zeros((B,), np.uint32)
        self._temp = np.ones((B,), np.float32)
        self._kpm = np.tile(_KPM_OFF, (B, 1))
        self._active = np.zeros((B,), bool)

        self._rows_in_place = False   # set when the step is traced
        self._step = self._build_decode_step()
        self._prefill = jax.jit(
            lambda p, toks: model.prefill(p, {"tokens": toks})[1]
        )
        # the caches are donated: each call's output takes over their
        # buffers, and ``self._caches`` is rebound to it
        self._insert = jax.jit(self._insert_impl, donate_argnums=0)
        self._seed_pair = jax.jit(_seed_pair)

        # metrics: {"dt": s, "active": n, "tokens": n} per decode step, dt
        # from the start of its engine.step span to its host sync
        self.step_times: List[Dict] = []
        self._steps = 0
        self._tokens_out = 0

        # asyncio surface
        self._running = False
        self._loop_task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None

    # -- planning ----------------------------------------------------------

    def _resolve_plans(self, B: int, V: int):
        """A u-driven sampler plan for the (B, V) decode workload.

        The per-slot RNG streams hand the draw an explicit (B,) uniform
        vector, so the plan is made key-less."""
        spec = self.model.cfg.sampler_spec

        def uplan(shape, devices=1):
            return sampling.plan(
                shape, method=spec.method, W=spec.W or None, dtype="float32",
                draws=1, has_key=False, devices=devices,
            )

        if self.mesh is None:
            return uplan((B, V)), None
        nd = _sharded.data_size(self.mesh)
        if B % nd:
            raise ValueError(
                f"max_slots={B} must divide over the mesh's {nd} data "
                "shards"
            )
        return None, uplan((B // nd, V), devices=nd)

    # -- compiled pieces ---------------------------------------------------

    def _build_decode_step(self):
        model, mesh = self.model, self.mesh
        plan, local_plan = self._plan, self._local_plan

        def draw(w, u, kpm):
            if mesh is None and plan.method in ("kernel", "kernel_trunc"):
                # ONE fused kernel: threshold bisection + walk in-tile
                from repro.kernels.butterfly_sample import ops as _kops

                return _kops.butterfly_sample_truncated(
                    w, u, kpm, W=plan.W, tb=plan.tb or 8, tk=plan.tk or 512
                )
            tau = _tr.thresholds_from_params(w, kpm)
            wm = jnp.where(w >= tau[:, None], w, jnp.zeros_like(w))
            if mesh is None:
                return _dist.draw(plan.build(wm), u=u)
            rs = _sharded.row_spec(mesh)

            def local(wm_l, u_l):
                return _dist.draw(local_plan.build(wm_l), u=u_l)

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(rs[0], None), rs), out_specs=rs,
                check_vma=False,  # pallas_call has no replication rule
            )(wm, u)

        @functools.partial(jax.jit, donate_argnums=1)
        def step(params, caches, token, pos, seeds, draw_idx, temp, kpm):
            # read as the model's cache write is chosen, when traced
            self._rows_in_place = _attn.rows_in_place()
            logits, caches = model.decode(params, caches, token[:, None], pos)
            # per-slot stream: uniform for (request seed, token index) —
            # independent of slot id, batch mix, and device count
            bits, _ = _rng.threefry2x32(
                seeds[:, 0], seeds[:, 1], draw_idx, jnp.zeros_like(draw_idx)
            )
            u = _rng.bits_to_uniform(bits)
            safe_t = jnp.where(temp > 0, temp, jnp.ones_like(temp))
            w = _dist.logits_to_weights(logits, safe_t).astype(jnp.float32)
            sampled = draw(w, u, kpm).astype(jnp.int32)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(temp > 0, sampled, greedy), caches

        return step

    @staticmethod
    def _insert_impl(caches, prefix, slot):
        """Write one request's prefilled prefix into a slot — and reset
        the slot's remaining rows in place (the zero pad), so no KV from
        the slot's previous occupant survives recycling."""

        def upd(path, big, small):
            if _attn.is_seq_leaf(path):
                pad = [(0, 0)] * small.ndim
                pad[2] = (0, big.shape[2] - small.shape[2])
                small = jnp.pad(small, pad)
            start = (0, slot) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), start
            )

        return jax.tree_util.tree_map_with_path(upd, caches, prefix)

    # -- submission --------------------------------------------------------

    def submit_nowait(self, req: Request) -> Request:
        """Admit a request (synchronous).  Raises ``ValueError`` when the
        request can't fit a slot's KV budget, :class:`QueueFullError`
        when admission control rejects it."""
        if req.total_budget > self.max_len:
            req.state = RequestState.REJECTED
            req.finish_reason = FinishReason.REJECTED
            obs.count("engine.rejected")
            raise ValueError(
                f"request needs {req.total_budget} KV positions "
                f"(prompt {req.prompt_len} + max_new {req.max_new_tokens}) "
                f"> engine max_len {self.max_len}"
            )
        if req.arrival_time < 0:
            req.arrival_time = time.perf_counter()
        try:
            return self.scheduler.submit(req)
        except QueueFullError:
            req.finish_reason = FinishReason.REJECTED
            obs.count("engine.rejected")
            if req.future is not None and not req.future.done():
                req.future.set_result(req)
            raise

    async def submit(self, req: Request) -> Request:
        """Asyncio admission: attaches a future resolved at finish."""
        loop = asyncio.get_running_loop()
        req.future = loop.create_future()
        self.submit_nowait(req)
        if self._wake is not None:
            self._wake.set()
        return req

    # -- the scheduling loop ------------------------------------------------

    def _admit(self) -> int:
        """Refill free slots from the queue head; at most ``prefill_chunk``
        prefills per call (0 = no cap) so decode latency stays bounded."""
        admitted = 0
        budget = self.prefill_chunk or self.max_slots
        for slot in self.scheduler.free_slots():
            if admitted >= budget:
                break
            req = self.scheduler.next_waiting()
            if req is None:
                break
            self._prefill_into(slot, req)
            self.scheduler.bind(slot, req)
            obs.count("engine.admitted")
            admitted += 1
        return admitted

    def _prefill_into(self, slot: int, req: Request) -> None:
        """Prefill a request's prompt into ``slot`` and set the slot's
        state.  The ``engine.admit`` span ends at the seed-pair readback,
        which waits for the prefill and the insert queued before it: the
        synced edge, stamped as ``req.prefill_time``."""
        start = time.perf_counter_ns()
        obs.record("engine.queue", int(req.arrival_time * 1e9), start,
                   req=req.id)
        req.state = RequestState.PREFILLING
        prefix = req.prompt[:-1]
        sb = _bucket(prefix.size) if prefix.size else 0
        with obs.span("engine.admit", req=req.id, bucket=sb,
                      prompt=req.prompt_len) as admit:
            if prefix.size:
                toks = np.zeros((1, sb), np.int32)
                toks[0, : prefix.size] = prefix
                pre = self._prefill(self.params, jnp.asarray(toks))
            else:
                # single-token prompt: no prefix — the insert still resets
                # the slot's rows with the zero-length (all-pad) prefix
                pre = self._empty_prefix
            self._caches = self._insert(self._caches, pre, jnp.int32(slot))
            # slot state: the prompt's LAST token runs through the decode
            # step at position prompt_len-1 (writes its own KV, yields the
            # first sampled token) — prefill logits are never consumed
            self._token[slot] = int(req.prompt[-1])
            self._pos[slot] = req.prompt_len - 1
            self._seeds[slot] = np.asarray(self._seed_pair(np.uint32(req.seed)))
            self._draw_idx[slot] = 0
            sp = req.sampling
            self._temp[slot] = req.effective_temperature(self.temperature)
            self._kpm[slot] = (
                float(sp.top_k or 0),
                float(1.0 if sp.top_p is None else sp.top_p),
                float(sp.min_p or 0.0),
            )
            self._active[slot] = True
        req.prefill_time = admit.end_ns * 1e-9

    def step_once(self) -> int:
        """One batched decode step over every slot.  Returns the number of
        live tokens produced (0 when no slot is active)."""
        if not self._active.any():
            return 0
        live = int(self._active.sum())
        with obs.span("engine.step", index=self._steps, live=live) as step:
            with obs.span("engine.step.dispatch"):
                nxt, self._caches = self._step(
                    self.params, self._caches,
                    jnp.asarray(self._token), jnp.asarray(self._pos),
                    jnp.asarray(self._seeds), jnp.asarray(self._draw_idx),
                    jnp.asarray(self._temp), jnp.asarray(self._kpm),
                )
            with obs.span("engine.step.wait") as wait:
                nxt_np = np.asarray(nxt)  # host sync: the step's wall-clock edge
            now = wait.end_ns * 1e-9
            with obs.span("engine.step.walk"):
                for slot in np.nonzero(self._active)[0]:
                    req = self.scheduler.bound(int(slot))
                    tok = int(nxt_np[slot])
                    if not req.output_tokens:
                        req.first_token_time = now
                    req.output_tokens.append(tok)
                    req.token_times.append(now)
                    self._token[slot] = tok
                    self._pos[slot] += 1
                    self._draw_idx[slot] += 1
                    eos = req.eos_id if req.eos_id is not None else self.eos_id
                    if eos is not None and tok == eos:
                        self._finish(int(slot), FinishReason.EOS)
                    elif len(req.output_tokens) >= req.max_new_tokens:
                        self._finish(int(slot), FinishReason.LENGTH)
        self.step_times.append(
            {"dt": (wait.end_ns - step.start_ns) * 1e-9, "active": live,
             "tokens": live}
        )
        self._steps += 1
        self._tokens_out += live
        obs.count("engine.tokens", live)
        if self._rows_in_place:
            obs.count("engine.cache_inplace")
        return live

    def _finish(self, slot: int, reason: FinishReason) -> None:
        req = self.scheduler.release(slot)
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.finish_time = time.perf_counter()
        self._active[slot] = False
        self._token[slot] = 0
        self._pos[slot] = 0
        self._draw_idx[slot] = 0
        self._temp[slot] = 1.0
        self._kpm[slot] = _KPM_OFF
        if req.future is not None and not req.future.done():
            req.future.set_result(req)

    def run(self, requests: Sequence[Request] = ()) -> List[Request]:
        """Synchronous drain: submit, then interleave admission and decode
        steps until queue and slots are empty."""
        out = []
        for r in requests:
            out.append(self.submit_nowait(r))
        while not self.scheduler.idle:
            self._admit()
            self.step_once()
        return out

    # -- asyncio surface ---------------------------------------------------

    async def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._wake = asyncio.Event()
        self._loop_task = asyncio.create_task(self._serve_loop())

    async def stop(self) -> None:
        self._running = False
        if self._wake is not None:
            self._wake.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None

    async def drain(self) -> None:
        """Wait until every admitted request has finished."""
        while not self.scheduler.idle:
            await asyncio.sleep(0.001)

    async def _serve_loop(self) -> None:
        while self._running:
            if self.scheduler.idle:
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.02)
                except asyncio.TimeoutError:
                    pass
                continue
            self._admit()
            self.step_once()
            # the step blocks this coroutine; yield so submissions whose
            # arrival times passed during it get admitted next iteration
            await asyncio.sleep(0)

    # -- introspection ------------------------------------------------------

    def warmup(self, max_prompt_len: int = 16, max_new_tokens: int = 2) -> None:
        """Trace everything a later run will touch: the decode step and
        each pow2 prefill bucket up to ``max_prompt_len``.  Metrics are
        reset after, so a post-warmup ``compile_stats()`` snapshot makes
        'zero retraces under churn' a checkable assertion."""
        lens, n = [], 1
        while n < max(1, max_prompt_len - 1):
            lens.append(n + 1)  # prefix of length n -> bucket n
            n *= 2
        lens.append(max(1, max_prompt_len))
        self.run([
            Request(
                prompt=np.zeros((ln,), np.int32),
                max_new_tokens=max_new_tokens,
                seed=i,
            )
            for i, ln in enumerate(lens)
        ])
        self.reset_metrics()

    def reset_metrics(self) -> None:
        self.step_times.clear()
        self._steps = 0
        self._tokens_out = 0

    def compile_stats(self) -> Dict[str, int]:
        """Trace/compile counters for the zero-retrace gate: after warmup
        ``decode_step_compiles`` must stay at 1 no matter what churns."""
        return {
            "decode_step_compiles": int(self._step._cache_size()),
            "prefill_compiles": int(self._prefill._cache_size()),
            "insert_compiles": int(self._insert._cache_size()),
            "plan_stats": sampling.plan_stats(),
        }

    def stats(self) -> Dict:
        sched = self.scheduler.stats
        return {
            **sched,
            "steps": self._steps,
            "tokens_out": self._tokens_out,
            "waiting": self.scheduler.waiting_depth,
            "active": self.scheduler.active_slots,
            "max_slots": self.max_slots,
        }
