"""Serving engines: continuous batching over a slotted KV cache.

The engine is the paper's construct at the request level: each submitted
request returns a *future* (its completion), the decode loop is the
stream, and chunked prefill (``prefill_chunk``) is the §7 chunk-size knob
balancing time-to-first-token against decode-step latency.

Two engines share one continuous-batching contract (``submit`` /
``step`` / ``run_until_drained``) and produce bit-identical greedy
outputs:

``Engine`` — the layer-sequential reference.  One monolithic jitted
``decode_step`` per decode step over all ``max_batch`` slots; admission,
sampling and retirement run in host Python between steps.

``StreamEngine`` — decode as a Stream program.  The transformer's layer
groups split into ``num_cells`` pipeline cells (params ride the chain's
read-only ``const_state``; each cell's cache shard is its mutable
Stream state, updated by row-level scatters only), the batch splits into
``microbatches`` in-flight items, and one ``Stream.feedback`` program
executes ``round_steps`` decode steps per device-program invocation:
the emitted token re-enters as the next item (lag = microbatches), and a
zipped *admission overlay* source plus per-cell admission buffers admit
freshly prefilled requests into retired slots **inside the plan** —
continuous batching realized by the schedule's feed carousel, not by
host Python.  Under ``FutureEvaluator`` the cells pipeline across a mesh
axis (gpipe / interleaved), hiding per-layer-group latency exactly as
the paper's Future substitution promises; under ``LazyEvaluator`` the
same program runs layer-sequentially on one device (the baseline
``bench_serve`` measures against).

Common architecture:
  * ``max_batch`` cache slots; per-slot length/active/eos state on host.
  * admit: new requests prefill in chunks (B=1, ragged tail padded to a
    single masked chunk) and enter a free slot — by host scatter
    (``Engine``) or by in-plan install (``StreamEngine``).
  * retire: slots retire on EOS, exhausted budget, or the ``max_len``
    cache boundary — including on the prefill-sampled first token; their
    futures resolve.
  * sampling: greedy argmax, or temperature sampling whose RNG derives
    from ``(seed, request uid, token index)`` — reproducible per request
    regardless of admission order, batching, or evaluator.

Request-lifecycle robustness (see also :mod:`repro.serve.supervisor`
for round-level fault recovery):
  * **bounded admission** — ``ServeConfig.max_queue`` caps the host
    queue; ``submit`` raises :class:`QueueFullError` (explicit load
    shedding) instead of queueing unboundedly under overload.
  * **deadlines** — ``submit(..., deadline_s=...)`` attaches a
    wall-clock budget; expired requests resolve with
    ``status="expired"`` at the next step boundary instead of holding a
    slot forever.
  * **cancellation** — ``cancel(uid)`` retires a queued or in-flight
    request through the normal retirement machinery (its slot frees for
    the next admission; takes effect at the next step/round boundary).
  * **honest drain** — ``run_until_drained`` raises
    :class:`DrainTimeoutError` naming the undrained uids when
    ``max_steps`` expires with requests still in flight, instead of
    silently truncating.

Observability.  ``engine.counters`` (:class:`EngineCounters`) holds
cumulative plain ints, each incremented where the work happens:

  * ``rounds`` — device rounds run (``Engine``: decode steps); over a
    wall-clock interval, the round rate.
  * ``tokens`` — decode tokens appended to requests (each request's
    first token, sampled at prefill, is counted by ``prefills``).
  * ``slot_steps`` — decode slot-steps computed: ``max_batch`` ×
    ``round_steps`` per round.  ``tokens / slot_steps`` is the share of
    the batch doing useful work; a low share with a queue means
    admission lags retirement.
  * ``prefills`` — requests prefilled; against submits, the backlog.
  * ``shed`` — submits refused by ``max_queue``: load beyond capacity.
  * ``cancelled`` — requests resolved by ``cancel``.
  * ``expired`` — requests resolved by their deadline: work the
    clients gave up on.

A supervisor's replay of a faulted round counts the replayed work
again: the counters count work done, not work delivered.

Host spans (``jax.profiler.TraceAnnotation``) mark the same boundaries;
they cost about a microsecond each with no profiler session open, and
with one open they land in the profiler's trace on the device clock.
``StreamEngine.step`` is ``serve.step`` (a step annotation, ``step_num``
the round count; metadata ``tokens`` and ``slot_steps``, that round's
counter deltas), holding ``serve.admit`` (deadlines and admission
planning, with one ``serve.prefill`` per admitted request: ``uid``,
``prompt_len``, ``queue_ms`` from submit to prefill), ``serve.inputs``
(the round's inputs), ``serve.dispatch`` (the round's call),
``serve.wait`` (the host blocked reading the round back) and
``serve.walk`` (the token walk and the host slot sync).  ``Engine``
opens ``serve.prefill`` only.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ArchConfig, DecodePipelineConfig
from repro.core import FutureEvaluator, LazyEvaluator, Stream
from repro.kernels import resolve_mode
from repro.models import layers as L
from repro.models import transformer as T

PyTree = Any


class QueueFullError(RuntimeError):
    """Load shedding: the admission queue is at ``max_queue``."""


class DrainTimeoutError(RuntimeError):
    """``run_until_drained`` hit ``max_steps`` with requests in flight."""

    def __init__(self, max_steps: int, undrained: list[int]):
        self.max_steps = max_steps
        self.undrained = undrained
        super().__init__(
            f"not drained after {max_steps} steps; "
            f"undrained request uids: {undrained}"
        )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_len: int = 1024
    prefill_chunk: int = 128
    max_new_tokens: int = 64
    eos_id: int = -1  # -1: never; run to max_new_tokens
    temperature: float = 0.0  # 0 => greedy
    attn_impl: str = "dense"
    seed: int = 0
    max_queue: int | None = None  # None: unbounded admission queue


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    deadline: float | None = None  # absolute time.monotonic() budget
    status: str = "ok"  # "ok" | "cancelled" | "expired"
    submitted: float = dataclasses.field(default_factory=time.monotonic)


@dataclasses.dataclass
class EngineCounters:
    """Cumulative counts of the engine's work; the module docstring
    names each and what an operator reads from it."""

    rounds: int = 0
    tokens: int = 0
    slot_steps: int = 0
    prefills: int = 0
    shed: int = 0
    cancelled: int = 0
    expired: int = 0


def sample_token(logits, temperature: float, seed: int, uid, ngen):
    """Sample the next token; reproducible per request.

    Greedy (``temperature <= 0``) is a plain argmax.  Temperature
    sampling derives its RNG key from ``(seed, uid, ngen)`` — the
    request uid and its token index — so retries, batch-mates, admission
    order and pipelined execution all sample identically.  ``logits``
    may be one row ``(V,)`` or a batch ``(B, V)`` with per-row
    uid/ngen; both engines call this one function (the StreamEngine from
    inside its emit), so host and device sampling share one code path.
    """
    logits = jnp.asarray(logits)
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    uid = jnp.asarray(uid, jnp.int32)
    ngen = jnp.asarray(ngen, jnp.int32)

    def one(lg, u, g):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), u), g
        )
        return jax.random.categorical(key, lg / temperature).astype(jnp.int32)

    if logits.ndim == 1:
        return one(logits, uid, ngen)
    return jax.vmap(one)(logits, uid, ngen)


class _EngineBase:
    """Shared request bookkeeping + chunked prefill."""

    def __init__(self, params, cfg: ArchConfig, scfg: ServeConfig):
        assert not cfg.embeds_input, "engine serves token-input archs"
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.lengths = np.zeros(scfg.max_batch, np.int32)
        self.active: list[Request | None] = [None] * scfg.max_batch
        self.queue: deque[Request] = deque()
        self._uid = 0
        self.counters = EngineCounters()
        # logits_at is passed traced (not static) so every ragged-tail
        # length shares one compiled prefill per chunk width.
        self._prefill = jax.jit(
            partial(T.prefill_step, cfg=cfg, attn_impl=scfg.attn_impl)
        )

    # -- public API ----------------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int | None = None,
        deadline_s: float | None = None,
    ) -> Request:
        """Returns the request handle (its .done flag is the future).

        ``deadline_s`` is a wall-clock budget from submission; an
        expired request resolves with ``status="expired"`` at the next
        step boundary.  With ``max_queue`` set, an over-full queue
        raises :class:`QueueFullError` — acceptance is explicit, so
        "zero accepted requests lost" is a meaningful contract.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if len(prompt) >= self.scfg.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} needs >= 1 free cache row; "
                f"max_len={self.scfg.max_len}"
            )
        mq = self.scfg.max_queue
        if mq is not None and len(self.queue) >= mq:
            self.counters.shed += 1
            raise QueueFullError(
                f"admission queue full ({len(self.queue)} >= max_queue={mq})"
            )
        req = Request(
            uid=self._uid,
            prompt=prompt,
            max_new_tokens=max_new_tokens or self.scfg.max_new_tokens,
            deadline=(
                None if deadline_s is None else time.monotonic() + deadline_s
            ),
        )
        self._uid += 1
        self.queue.append(req)
        return req

    def cancel(self, uid: int) -> bool:
        """Retire a queued or in-flight request host-side.

        The request resolves immediately (``done=True``,
        ``status="cancelled"``, tokens so far kept); an occupied slot is
        released through the normal retirement machinery, so the next
        admission reuses it.  For the StreamEngine the device round in
        progress is untouched — the cancelled slot simply stops
        re-entering at the next round boundary, exactly like an EOS
        retirement.  Returns False for unknown/finished uids.
        """
        for req in list(self.queue):
            if req.uid == uid and not req.done:
                self.queue.remove(req)
                req.done, req.status = True, "cancelled"
                self.counters.cancelled += 1
                return True
        for slot, req in enumerate(self.active):
            if req is not None and req.uid == uid and not req.done:
                req.done, req.status = True, "cancelled"
                self._retire_slot(slot)
                self.counters.cancelled += 1
                return True
        return False

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        finished = []
        for _ in range(max_steps):
            finished.extend(self.step())
            if not self.queue and all(r is None for r in self.active):
                return finished
        undrained = sorted(
            [r.uid for r in self.queue]
            + [r.uid for r in self.active if r is not None]
        )
        raise DrainTimeoutError(max_steps, undrained)

    def step(self) -> list[Request]:  # pragma: no cover - abstract
        raise NotImplementedError

    # -- internals -----------------------------------------------------------

    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _retire_slot(self, slot: int) -> None:
        """Release a slot host-side (cancel/expiry); cache rows are
        stale-but-inert until the next admission overwrites them."""
        self.active[slot] = None

    def _expire_deadlines(self) -> list[Request]:
        """Resolve requests whose deadline has passed; returns them.
        Called at each step boundary — queued requests are dropped
        before ever prefetching, in-flight ones retire their slot."""
        now = time.monotonic()
        expired = []
        for req in list(self.queue):
            if req.deadline is not None and now >= req.deadline:
                self.queue.remove(req)
                req.done, req.status = True, "expired"
                expired.append(req)
        for slot, req in enumerate(self.active):
            if req is not None and req.deadline is not None and now >= req.deadline:
                req.done, req.status = True, "expired"
                self._retire_slot(slot)
                expired.append(req)
        self.counters.expired += len(expired)
        return expired

    def _sample_host(self, logits_row: np.ndarray, uid: int, ngen: int) -> int:
        if self.scfg.temperature <= 0:
            # Same first-max tie-breaking as jnp.argmax in the device
            # emit, without a per-slot device dispatch on the hot path.
            return int(np.argmax(logits_row))
        return int(
            sample_token(
                logits_row, self.scfg.temperature, self.scfg.seed, uid, ngen
            )
        )

    def _prefill_single(self, req: Request) -> tuple[PyTree, bool]:
        """Chunked prefill of one request into a fresh single-slot cache.

        Full ``prefill_chunk``-sized chunks stream through the cache; the
        ragged tail (``plen % prefill_chunk``) is padded to one masked
        chunk whose logits are read at the last real position — one call
        instead of one B=1 decode per tail token, which is where most of
        a short prompt's TTFT went (see ``benchmarks/bench_serve.py``).
        Samples the first token (ngen=0) and applies retirement to it:
        EOS, a budget of 1, or a prompt at the ``max_len`` boundary
        complete without ever occupying a batch slot.
        Returns ``(single_cache, done)``.
        """
        ck = self.scfg.prefill_chunk
        prompt = req.prompt
        plen = len(prompt)
        self.counters.prefills += 1
        with TraceAnnotation(
            "serve.prefill", uid=req.uid, prompt_len=plen,
            queue_ms=1e3 * (time.monotonic() - req.submitted),
        ):
            full = (plen // ck) * ck
            single = T.init_cache(self.cfg, 1, self.scfg.max_len)
            logits = None
            for c in range(full // ck):
                chunk = jnp.asarray(prompt[None, c * ck : (c + 1) * ck])
                logits, single = self._prefill(
                    self.params, single, tokens=chunk, pos=c * ck
                )
            rem = plen - full
            if rem:
                # Pad the tail to one masked chunk — clamped to the cache
                # end so the write can never clamp-and-corrupt earlier rows
                # when max_len is not a multiple of the chunk size.
                width = min(ck, self.scfg.max_len - full)
                tail = np.zeros((1, width), np.int32)
                tail[0, :rem] = prompt[full:]
                logits, single = self._prefill(
                    self.params, single,
                    tokens=jnp.asarray(tail), pos=full,
                    logits_at=jnp.asarray(rem - 1, jnp.int32),
                )
            tok = self._sample_host(np.asarray(logits)[0], req.uid, 0)
        req.out_tokens.append(tok)
        done = (
            len(req.out_tokens) >= req.max_new_tokens
            or tok == self.scfg.eos_id
            or plen + 1 >= self.scfg.max_len
        )
        return single, done


class Engine(_EngineBase):
    """Layer-sequential reference engine (monolithic jitted decode_step)."""

    def __init__(self, params, cfg: ArchConfig, scfg: ServeConfig):
        super().__init__(params, cfg, scfg)
        self.cache = T.init_cache(cfg, scfg.max_batch, scfg.max_len)
        self._decode = jax.jit(
            partial(T.decode_step, cfg=cfg, attn_impl=scfg.attn_impl)
        )

    # -- internals -----------------------------------------------------------

    def _admit(self) -> list[Request]:
        finished = []
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                break
            req = self.queue.popleft()
            single, done = self._prefill_single(req)
            if done:
                req.done = True
                finished.append(req)
                continue  # slot stays free for the next queued request
            # Scatter this request's cache rows into the batch cache.
            def insert(batch_leaf, single_leaf):
                return batch_leaf.at[:, slot].set(single_leaf[:, 0])

            self.cache = jax.tree.map(insert, self.cache, single)
            self.lengths[slot] = len(req.prompt)
            self.active[slot] = req
        return finished

    def step(self) -> list[Request]:
        """Admit, one batched decode step, retire. Returns newly finished."""
        finished = self._expire_deadlines()
        finished.extend(self._admit())
        slots = [i for i, r in enumerate(self.active) if r is not None]
        if not slots:
            return finished
        # last token per active slot (prefill-sampled or last generated)
        tokens = np.zeros(self.scfg.max_batch, np.int32)
        for i in slots:
            tokens[i] = self.active[i].out_tokens[-1]
        logits, self.cache = self._decode(
            self.params, self.cache,
            tokens=jnp.asarray(tokens),
            lengths=jnp.asarray(self.lengths),
        )
        self.counters.rounds += 1
        self.counters.slot_steps += self.scfg.max_batch
        self.counters.tokens += len(slots)
        logits = np.asarray(logits)
        if self.scfg.temperature > 0:
            # One batched draw for all active slots (the same vmapped
            # path the StreamEngine's emit uses) instead of a per-slot
            # device dispatch on the decode hot path.
            uids = np.array([self.active[i].uid for i in slots], np.int32)
            ngens = np.array(
                [len(self.active[i].out_tokens) for i in slots], np.int32
            )
            drawn = np.asarray(
                sample_token(
                    logits[slots], self.scfg.temperature, self.scfg.seed,
                    uids, ngens,
                )
            )
            sampled = dict(zip(slots, drawn))
        else:
            sampled = {i: np.argmax(logits[i]) for i in slots}
        for i in slots:
            req = self.active[i]
            self.lengths[i] += 1
            tok = int(sampled[i])
            req.out_tokens.append(tok)
            hit_eos = tok == self.scfg.eos_id
            full = self.lengths[i] + 1 >= self.scfg.max_len
            if len(req.out_tokens) >= req.max_new_tokens or hit_eos or full:
                req.done = True
                finished.append(req)
                self.active[i] = None
        return finished


def decode_copy_bytes_per_tick(
    cfg: ArchConfig,
    microbatch: int,
    num_cells: int,
    *,
    row_scatter: bool = True,
    max_len: int = 1024,
) -> int:
    """Bytes one steady decode tick writes into its cell's cache shard.

    Under the row-scatter update scheme (the shipped hot path) a tick
    writes exactly one cache row per sequence per layer — the
    ``max_len=1`` cache layout *is* that row set, so its byte count over
    ``num_cells`` is the per-tick traffic.  Cross-attention vision K/V
    never changes during decode (``scatter_decode_rows`` skips it), so
    its leaves are excluded from the row set.  ``row_scatter=False``
    models the slab scheme this replaced (slice-out/slice-in of the
    whole microbatch block, vision K/V included — the old path rewrote
    it): the attention/SSM leaves at full ``max_len`` — a ``max_len``×
    larger term.  Feed the result through
    :func:`repro.core.chunking.copy_time_per_tick` into
    :func:`repro.core.chunking.optimal_schedule`'s ``per_tick_copy``.
    """
    layout = T.cache_layout(cfg, microbatch, 1 if row_scatter else max_len)
    if row_scatter:
        plans = T.block_plans(cfg)
        layout = {
            key: blk
            for key, blk in layout.items()
            if plans[int(key.removeprefix("block"))].mixer != "cross_attn"
        }
    total = sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(layout)
    )
    return total // num_cells


def suggest_decode_pipeline(
    cfg: ArchConfig,
    *,
    devices: int,
    work_per_item: float,
    per_tick_overhead: float,
    microbatch: int,
    num_cells: int,
    copy_bytes_per_second: float = 50e9,
    max_len: int = 1024,
    row_scatter: bool = True,
    max_chunks: int = 64,
):
    """Pick a decode (schedule, M, V) with the cache-traffic term included.

    Thin serving-side threading of the chunking cost model: converts the
    per-tick copy bytes of the decode cells (row-scatter or slab) into a
    time term and hands it to
    :func:`repro.core.chunking.optimal_schedule`.  Returns a
    :class:`repro.core.chunking.ScheduleChoice`.
    """
    from repro.core import chunking

    per_tick_copy = chunking.copy_time_per_tick(
        decode_copy_bytes_per_tick(
            cfg, microbatch, num_cells,
            row_scatter=row_scatter, max_len=max_len,
        ),
        copy_bytes_per_second,
    )
    return chunking.optimal_schedule(
        work_per_item,
        devices,
        per_tick_overhead,
        max_chunks=max_chunks,
        per_tick_copy=per_tick_copy,
    )


def _overlay_combine(flow, src):
    """Entry-zip admission overlay: where ``gate`` is set, the slot's
    row is replaced wholesale by the admitted request's state (its
    prefill-sampled token, re-embedded hidden state, prompt length and
    budget) — the outgoing retired occupant simply stops re-entering."""
    gate = src["gate"]

    def sel(f, a):
        g = gate.reshape(gate.shape + (1,) * (f.ndim - 1))
        return jnp.where(g, a, f)

    out = dict(flow)
    for k in ("x", "tok", "pos", "active", "uid", "ngen", "budget"):
        out[k] = sel(flow[k], src[k])
    return out


class StreamEngine(_EngineBase):
    """Decode as a pipelined ``Stream.feedback`` program.

    One round = ``round_steps`` decode steps of all ``microbatches``
    in-flight items, executed as a single device program: items flow
    through ``num_cells`` layer-group cells, the emit (final-norm →
    logits → sample → re-embed) feeds each item's token back in with lag
    ``microbatches``, and admissions planned at round start (free slots,
    plus slots whose budget provably retires mid-round) are installed by
    the cells themselves the tick they first see the admission's item.
    With ``mesh=None`` the same program runs under ``LazyEvaluator`` —
    stream-shaped but layer-sequential, the pipelining ablation.
    """

    def __init__(
        self,
        params,
        cfg: ArchConfig,
        scfg: ServeConfig,
        pcfg: DecodePipelineConfig | None = None,
        mesh: jax.sharding.Mesh | None = None,
    ):
        super().__init__(params, cfg, scfg)
        pcfg = pcfg or DecodePipelineConfig()
        self.pcfg = pcfg
        if scfg.max_batch % pcfg.microbatches != 0:
            raise ValueError(
                f"max_batch={scfg.max_batch} not divisible by "
                f"microbatches={pcfg.microbatches}"
            )
        if pcfg.admit_per_round < 1:
            raise ValueError(
                "admit_per_round must be >= 1 (with 0 no request could "
                "ever enter a slot and run_until_drained would spin)"
            )
        self.mb_size = scfg.max_batch // pcfg.microbatches
        groups = cfg.num_layers // T.effective_period(cfg)
        if groups % pcfg.num_cells != 0:
            raise ValueError(
                f"{groups} layer groups not divisible by "
                f"num_cells={pcfg.num_cells}"
            )
        if mesh is None:
            self.evaluator = LazyEvaluator()
        else:
            self.evaluator = FutureEvaluator(
                mesh,
                pcfg.axis_name,
                schedule=pcfg.schedule,
                interleave=pcfg.interleave,
            )
        # Read-only/mutable split: layer params ride the Stream's
        # const_state (scan xs, stage-sharded, never written back); the
        # per-cell cache shard is the only mutable state.
        self.cell_consts, self.cell_states = T.split_decode_cells(
            params, T.init_cache(cfg, scfg.max_batch, scfg.max_len),
            pcfg.num_cells,
        )
        if mesh is not None:
            # Place each cell's weights and cache shard on its stage once:
            # left on the default device, the whole chain would sit on
            # device 0 and be copied out to the stages every round.
            stage = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(pcfg.axis_name)
            )
            self.cell_consts, self.cell_states = jax.device_put(
                (self.cell_consts, self.cell_states), stage
            )
        # Kernel dispatch for the hot path: the pipeline knob overrides
        # the model knob; resolved once ("auto" -> backend) so cells and
        # emit agree.  A failing fused kernel raises: there is no silent
        # fallback that would serve from a different path.
        self.kernels = resolve_mode(
            cfg.kernels if pcfg.kernels is None else pcfg.kernels
        )
        self._zero_single = T.init_cache(cfg, 1, scfg.max_len)

        # A named function, so that traces call the program by its name.
        def embed_tokens(toks):
            return L.embed_lookup(params["embed"]["embedding"], toks)

        self._embed = jax.jit(embed_tokens)
        self._by_uid: dict[int, Request] = {}
        self._cell_fn = T.make_decode_cell(
            cfg,
            num_cells=pcfg.num_cells,
            microbatch=self.mb_size,
            attn_impl=scfg.attn_impl,
            admissions=pcfg.admit_per_round,
            kernels=self.kernels,
        )
        self._emit = T.make_decode_emit(
            params, cfg,
            sample_fn=lambda lg, uid, ngen: sample_token(
                lg, scfg.temperature, scfg.seed, uid, ngen
            ),
            eos_id=scfg.eos_id,
            max_len=scfg.max_len,
            kernels=self.kernels,
        )
        t_, m_ = pcfg.round_steps, pcfg.microbatches

        def _round(cell_consts, cell_states, init_items, overlay_items):
            program = (
                Stream.feedback(init_items, t_ * m_, self._emit)
                .zip(Stream.source(overlay_items), _overlay_combine)
                .through(
                    self._cell_fn, cell_states, const_state=cell_consts
                )
            )
            res = program.collect(self.evaluator)
            return res.states[0], res.items

        # Donate the mutable cell states (the KV cache): the round's
        # output caches reuse the input buffers in place — the hot loop
        # allocates no second cache.  (CPU ignores donation; skip the
        # per-call warning there.)
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._round = jax.jit(_round, donate_argnums=donate)

    @property
    def cache(self) -> PyTree:
        """The batch cache, re-merged from per-cell shards (inspection)."""
        return T.merge_decode_caches(self.cell_states)

    # -- round construction --------------------------------------------------

    def _plan_admissions(self, t_: int):
        """(slot, step, request) admissions for the coming round.

        Free slots admit at step 0.  A slot whose occupant provably
        exhausts its budget at round-local step k-1 is free at step k
        (EOS may free it earlier — admitting at k is then merely late,
        never wrong), so queued requests keep entering mid-flight.
        Requests that retire on their prefill-sampled token never occupy
        a slot.  Returns (admissions, finished_at_prefill).
        """
        import heapq

        a_max = self.pcfg.admit_per_round
        finished: list[Request] = []
        admissions: list[tuple[int, int, Request, PyTree]] = []
        events: list[tuple[int, int]] = []  # (step, slot), earliest first
        for slot, req in enumerate(self.active):
            if req is None:
                events.append((0, slot))
            else:
                k = req.max_new_tokens - len(req.out_tokens)
                if k < t_:
                    events.append((k, slot))
        heapq.heapify(events)
        while self.queue and len(admissions) < a_max and events:
            step, slot = heapq.heappop(events)
            while self.queue:
                req = self.queue.popleft()
                single, done = self._prefill_single(req)
                self._by_uid[req.uid] = req
                if done:
                    req.done = True
                    finished.append(req)
                    continue  # slot still free: try the next request
                admissions.append((slot, step, req, single))
                # This request may itself retire mid-round: its slot
                # frees again once its remaining budget is spent.
                k2 = step + (req.max_new_tokens - len(req.out_tokens))
                if k2 < t_:
                    heapq.heappush(events, (k2, slot))
                break
        return admissions, finished

    def _build_round_inputs(self, admissions):
        scfg, pcfg = self.scfg, self.pcfg
        b_, m_, t_ = scfg.max_batch, pcfg.microbatches, pcfg.round_steps
        bm = self.mb_size
        tok = np.zeros(b_, np.int32)
        active = np.zeros(b_, bool)
        uid = np.zeros(b_, np.int32)
        ngen = np.zeros(b_, np.int32)
        budget = np.ones(b_, np.int32)
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            tok[slot] = req.out_tokens[-1]
            active[slot] = True
            uid[slot] = req.uid
            ngen[slot] = len(req.out_tokens)
            budget[slot] = req.max_new_tokens
        x = np.asarray(self._embed(jnp.asarray(tok)))[:, None, :]
        init_items = {
            "x": jnp.asarray(x.reshape(m_, bm, 1, -1)),
            "tok": jnp.asarray(tok.reshape(m_, bm)),
            "pos": jnp.asarray(self.lengths.reshape(m_, bm)),
            "active": jnp.asarray(active.reshape(m_, bm)),
            "uid": jnp.asarray(uid.reshape(m_, bm)),
            "ngen": jnp.asarray(ngen.reshape(m_, bm)),
            "budget": jnp.asarray(budget.reshape(m_, bm)),
            "mb": jnp.arange(m_, dtype=jnp.int32),
            "step": jnp.zeros(m_, jnp.int32),
        }

        n = t_ * m_
        ov = {
            "gate": np.zeros((n, bm), bool),
            "tok": np.zeros((n, bm), np.int32),
            "pos": np.zeros((n, bm), np.int32),
            "active": np.zeros((n, bm), bool),
            "uid": np.zeros((n, bm), np.int32),
            "ngen": np.zeros((n, bm), np.int32),
            "budget": np.ones((n, bm), np.int32),
        }
        singles, slots, steps, mbs = [], [], [], []
        for slot, step, req, single in admissions:
            mb, row = divmod(slot, bm)
            b = step * m_ + mb
            ov["gate"][b, row] = True
            ov["tok"][b, row] = req.out_tokens[-1]
            ov["pos"][b, row] = len(req.prompt)
            ov["active"][b, row] = True
            ov["uid"][b, row] = req.uid
            ov["ngen"][b, row] = len(req.out_tokens)
            ov["budget"][b, row] = req.max_new_tokens
            singles.append(single)
            slots.append(slot)
            steps.append(step)
            mbs.append(mb)
        # Pad the admission buffer to its static depth; step -1 never fires.
        while len(singles) < self.pcfg.admit_per_round:
            singles.append(self._zero_single)
            slots.append(0)
            steps.append(-1)
            mbs.append(-1)
        adm = T.stack_admission_payload(
            singles, slots, steps, mbs, self.pcfg.num_cells
        )
        # Embed only the gated rows (at most admit_per_round of them) —
        # everything else in the overlay is a zero the combine discards.
        ov_x = np.zeros((n, bm, 1, x.shape[-1]), x.dtype)
        gated = np.argwhere(ov["gate"])
        if len(gated):
            emb = np.asarray(
                self._embed(jnp.asarray(ov["tok"][gated[:, 0], gated[:, 1]]))
            )
            ov_x[gated[:, 0], gated[:, 1], 0] = emb
        overlay = {k: jnp.asarray(v) for k, v in ov.items()}
        overlay["x"] = jnp.asarray(ov_x)
        return init_items, overlay, adm

    # -- the round -----------------------------------------------------------

    def step(self) -> list[Request]:
        """One pipelined round of ``round_steps`` decode steps."""
        c = self.counters
        tokens, slot_steps = c.tokens, c.slot_steps
        with StepTraceAnnotation("serve.step", step_num=c.rounds) as span:
            finished = self._step()
            span.set_metadata(
                tokens=c.tokens - tokens, slot_steps=c.slot_steps - slot_steps
            )
        return finished

    def _step(self) -> list[Request]:
        t_ = self.pcfg.round_steps
        with TraceAnnotation("serve.admit"):
            finished = self._expire_deadlines()
            admissions, planned = self._plan_admissions(t_)
            finished.extend(planned)
            for slot, req in enumerate(self.active):
                if req is not None:
                    self._by_uid[req.uid] = req
        if not admissions and all(r is None for r in self.active):
            return finished
        with TraceAnnotation("serve.inputs"):
            init_items, overlay, adm = self._build_round_inputs(admissions)
        # The admission payload is read-only within a round, so it rides
        # const_state — it never enters the mutable carry, and nothing
        # needs dropping afterwards (const state is not returned).
        with TraceAnnotation("serve.dispatch"):
            new_states, collected = self._round(
                {**self.cell_consts, "adm": adm},
                self.cell_states, init_items, overlay,
            )
        self.cell_states = new_states
        self.counters.rounds += 1
        self.counters.slot_steps += t_ * self.scfg.max_batch
        with TraceAnnotation("serve.wait"):
            col = {
                k: np.asarray(collected[k])
                for k in ("tok", "pos", "active", "uid", "ngen")
            }
        with TraceAnnotation("serve.walk"):
            finished.extend(self._walk(col))
        return finished

    def _walk(self, col: dict[str, np.ndarray]) -> list[Request]:
        """Hand the round's tokens to their requests, then sync the host's
        slot state; returns the requests that finished."""
        t_, m_ = self.pcfg.round_steps, self.pcfg.microbatches
        bm = self.mb_size
        finished = []
        appended = 0
        # Walk emitted items in stream order; a row's token is real when
        # its ngen is one past what the host has — frozen (retired) rows
        # repeat their ngen and are skipped, exactly mirroring the emit.
        for b in range(t_ * m_):
            for r in range(bm):
                req = self._by_uid.get(int(col["uid"][b, r]))
                if req is None or req.done:
                    continue
                g = int(col["ngen"][b, r])
                if g != len(req.out_tokens) + 1:
                    continue
                tok = int(col["tok"][b, r])
                req.out_tokens.append(tok)
                appended += 1
                done = (
                    g >= req.max_new_tokens
                    or tok == self.scfg.eos_id
                    or int(col["pos"][b, r]) + 1 >= self.scfg.max_len
                )
                if done:
                    req.done = True
                    finished.append(req)
        self.counters.tokens += appended
        # Host slot state syncs from each microbatch's final item.
        for mb in range(m_):
            b = (t_ - 1) * m_ + mb
            for r in range(bm):
                slot = mb * bm + r
                self.lengths[slot] = int(col["pos"][b, r])
                req = self._by_uid.get(int(col["uid"][b, r]))
                live = bool(col["active"][b, r]) and req is not None and not req.done
                self.active[slot] = req if live else None
        self._by_uid = {
            r.uid: r for r in self.active if r is not None
        }
        return finished
