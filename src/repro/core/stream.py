"""Stream re-interpreted with a substitutable evaluation monad.

This is the JAX port of the paper's central construct:

    class Cons(hd: A, tl: Future[Stream[A]]) extends Stream[A]

**The front door is the combinator algebra** (:mod:`repro.core.graph`)::

    from repro.core import Stream

    Stream.source(items)                 # M items, leading axis = stream
          .map(f)                        # stateless per-item transform
          .through(cell_fn, states)      # chain segment of dependent cells
          .zip(other, combine)           # multi-source item-by-item merge
          .concat(other)                 # sequential composition
          .mask(pred)                    # bounded-stream validity tagging
          .collect(evaluator)            # run -> StreamResult(items, states)

Combinators build a :class:`~repro.core.graph.StreamGraph` IR that both
evaluators execute.  A chain segment's cell owns mutable per-cell state
and transforms the item flowing through it::

    cell_fn : (state_s, item) -> (state_s', item')

The semantics are fixed and evaluator-independent:

    item b reaches cell s only after item b-1 has left cell s, and after
    item b has left cell s-1; item b of ``x.zip(y, f)`` is
    ``f(x[b], y[b])`` — source order, never arrival order.

Two evaluators implement these semantics — the paper's Lazy/Future monad
substitution:

* :class:`LazyEvaluator` — topological composition of ``lax.scan``s over
  the IR on the local device.  Sequential, memoized carry: the Lazy
  monad.  Executes *any* well-formed graph, including zips whose both
  sides carry stateful segments.
* :class:`FutureEvaluator` — a **schedule-pluggable pipeline engine**.
  The graph is lowered (:func:`repro.core.graph.lower_chain`) to a spine
  of fused chain segments plus per-source injection points; cells are
  sharded across a mesh axis and a host-built
  :class:`repro.core.schedules.SchedulePlan` (``gpipe``, ``one_f_one_b``
  or ``interleaved``) dictates, per tick, which microbatch each device
  advances and through which of its local cell groups.  The inter-stage
  hand-off is a ring ``ppermute`` routed through
  :func:`repro.core.future.ppermute_future`: the collective is *issued
  before* the tick's ``lax.scan`` over local cells and *forced after*
  it, so the permute is in flight during compute (the future is the
  mechanism, not a metaphor).  **Every source** — one per ``zip`` branch
  — is round-robin sharded over the stage axis (with a rotation offset
  so its items arrive at its injection device on time) and delivered by
  its own reverse-ring feed carousel at its own virtual stage: a zip of
  two sources pipelines with no per-stage replication of either.
  Outputs accumulate only on the last stage and leave the region as a
  stage-sharded buffer (no ``psum`` replication — the caller takes the
  last stage's shard with one static slice).

Both produce bit-identical results (tested, including under hypothesis);
only the schedule differs.  This mirrors the paper's claim that the
algorithm text is unchanged when substituting Future for Lazy — and,
one level up, that the *schedule* can change without touching either.

All constructs (scan, ppermute, switch, where, dynamic slicing, the
barrier in ``force``) are differentiable, so ``jax.grad`` through any
schedule yields the reversed backward pipeline automatically.

Unbounded streams do not exist on XLA (shape-static); the paper itself
bounds the stream in its Future version ("otherwise the computation will
not stop since it is asynchronous").  We adopt the same concession:
streams are bounded, with ``.mask`` validity where needed.

**Migration note** — :class:`StreamProgram` survives as a thin
deprecated adapter over a one-segment graph::

    evaluate(StreamProgram(cell, states, n), items, ev)   # still works
    Stream.from_program(program, items).collect(ev)       # same thing
    Stream.source(items).through(cell, states).collect(ev)  # the new way

New code should build streams with the algebra; multi-source programs
(``zip``/``concat``) have no ``StreamProgram`` spelling.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import graph as G
from repro.core.future import ppermute_future
from repro.core.graph import Stream, StreamResult
from repro.core.schedules import (
    SchedulePlan,
    build_backward_plan,
    build_plan,
    validate_backward,
)

PyTree = Any
CellFn = Callable[[PyTree, PyTree], tuple[PyTree, PyTree]]

# The pipeline regions run without JAX's varying-manual-axes type check.
# Under it, the Pallas interpreter that runs the fused serving kernels
# off-TPU cannot evaluate a kernel (its block slicing indexes varying
# operands with the invariant grid index), and every zero-initialized
# loop carry would need a cast to varying.
_CHECK_VMA = False


# ---------------------------------------------------------------------------
# Program (deprecated adapter)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamProgram:
    """A bounded stream of ``num_cells`` dependent cells.

    .. deprecated::
        The combinator algebra (:class:`repro.core.graph.Stream`) is the
        public front door; ``StreamProgram`` remains as an adapter for a
        one-segment chain (``Stream.from_program``) so existing call
        sites migrate incrementally.

    Attributes:
      cell_fn: ``(state, item) -> (new_state, out_item)``.  Pure.  Applied
        once per (cell, item) pair.  The cell index, if needed, should be
        carried inside ``state`` (see :func:`indexed_states`).
      init_state: per-cell state, every leaf stacked with leading axis
        ``num_cells``.
      num_cells: chain length (the paper's stream length).
    """

    cell_fn: CellFn
    init_state: PyTree
    num_cells: int
    # False => cells never mutate their state (e.g. the state is layer
    # parameters).  Evaluators then skip the masked state write-back, which
    # would otherwise materialize a full copy of the state per tick.
    mutable_state: bool = True
    # Rematerialize cell_fn on the backward pass (GPipe-style activation
    # checkpointing per (cell, item) pair).
    remat: bool = False

    def __post_init__(self):
        leaves = jax.tree.leaves(self.init_state)
        for leaf in leaves:
            if hasattr(leaf, "shape") and leaf.shape[:1] != (self.num_cells,):
                raise ValueError(
                    f"init_state leaves must have leading axis num_cells="
                    f"{self.num_cells}, got shape {leaf.shape}"
                )


def indexed_states(state: PyTree, num_cells: int) -> PyTree:
    """Attach a cell-index leaf to per-cell state (helper)."""
    return {"index": jnp.arange(num_cells), "state": state}


def _check_program(program, items) -> bool:
    """Shared Stream/StreamProgram dispatch + item validation.

    Returns True for the legacy StreamProgram form (items validated),
    False for a Stream (which carries its own sources).
    """
    if isinstance(program, Stream):
        if items is not None:
            raise ValueError(
                "a Stream carries its own sources; do not pass items"
            )
        return False
    if isinstance(program, StreamProgram):
        G.leading_axis_size(items, "items")
        return True
    raise TypeError(
        f"expected Stream or StreamProgram, got {type(program).__name__}"
    )


def _as_chain(program, items) -> tuple[G.ChainProgram, bool]:
    """Normalize (StreamProgram, items) | Stream into a ChainProgram.

    Returns ``(chain, legacy)`` — legacy callers get the single
    segment's states back un-tupled.  Builds the one-segment graph
    directly (``Stream.from_program`` warns on use; the adapter itself
    must not).
    """
    if _check_program(program, items):
        stream = Stream.source(items).through(
            program.cell_fn,
            program.init_state,
            num_cells=program.num_cells,
            mutable_state=program.mutable_state,
            remat=program.remat,
        )
        return stream.lower(), True
    return program.lower(), False


# ---------------------------------------------------------------------------
# Lazy evaluator — the Lazy monad (sequential, memoized)
# ---------------------------------------------------------------------------


class LazyEvaluator:
    """Sequential evaluation: topological lax.scan composition of the IR.

    Equivalent to the paper's ``Future(value: => A)`` with ``lazy val``
    memoization — every tail is evaluated exactly once, on demand, on the
    calling thread.  Runs any well-formed graph, including those the
    pipeline lowering rejects (zips of two stateful pipelines).
    """

    name = "lazy"

    def run_graph(self, stream: Stream) -> StreamResult:
        if any(isinstance(n, G.FeedbackNode) for n in stream.nodes()):
            # Feedback has no node-local order; run the lowered chain
            # sequentially (same per-cell primitive sequence as the
            # Future engine, so bit-equality holds for unfolds too).
            states, outs = G.run_chain_sequential(stream.lower())
            return StreamResult(items=outs, states=states)
        outs, states = G.lazy_eval_graph(stream.node)
        return StreamResult(items=outs, states=states)

    def __call__(self, program, items: PyTree = None) -> tuple[PyTree, PyTree]:
        """Run ``items`` (leading axis = stream of M items) through the chain.

        Returns ``(final_states, out_items)`` with ``out_items`` leading
        axis M (item b after all cells).  ``program`` may be a deprecated
        :class:`StreamProgram` (with ``items``) or a :class:`Stream`
        (whose sources carry the items; final states are a tuple, one per
        segment).
        """
        if not _check_program(program, items):
            result = self.run_graph(program)
            return result.states, result.items

        cell_fn = (
            jax.checkpoint(program.cell_fn) if program.remat else program.cell_fn
        )

        def item_step(states, item):
            def cell(flowing, state):
                new_state, out = cell_fn(state, flowing)
                if not program.mutable_state:
                    new_state = state
                return out, new_state

            out, new_states = lax.scan(cell, item, states)
            return new_states, out

        return lax.scan(item_step, program.init_state, items)


# ---------------------------------------------------------------------------
# Future evaluator — the schedule-pluggable pipeline engine
# ---------------------------------------------------------------------------


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _round_robin_feed(x, num_stages: int, n_items: int, offset: int = 0,
                      flip: bool = False):
    """Shard one leaf's item axis round-robin over the stage axis.

    Returns ``(D, ceil(n/D), ...)``: device ``d``'s local feed shard.
    ``offset`` rotates the layout so item ``m`` reaches the injection
    device after ``m`` reverse-ring advances (the forward carousels);
    ``flip`` mirrors it instead — device ``d`` holds items
    ``j*D + (D-1-d)`` and the carousel advances on the *forward* ring,
    so item ``m`` reaches device ``D-1`` at its m-th consumption (the
    planned backward's cotangent-seed carousel).
    """
    d_ = num_stages
    feed_len = math.ceil(n_items / d_)
    pad = feed_len * d_ - n_items
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    x = x.reshape((feed_len, d_) + x.shape[1:])
    if flip:
        x = x[:, ::-1]
    elif offset:
        x = jnp.roll(x, offset, axis=1)
    return jnp.swapaxes(x, 0, 1)


class FutureEvaluator:
    """Pipelined evaluation across ``axis_name`` of ``mesh``.

    The program (a :class:`Stream` or deprecated :class:`StreamProgram`)
    is lowered to a :class:`~repro.core.graph.ChainProgram` — a spine of
    fused chain segments plus one injection point per source.  The total
    cell count must be divisible by ``D * interleave`` where D is the
    axis size, and every interior injection (``zip``) must fall on a
    virtual-stage boundary.  With ``interleave == 1`` device d owns one
    contiguous group of cells (one stage); with ``interleave == V > 1``
    it owns V non-contiguous groups (virtual stages ``v*D + d`` — the
    interleaved schedule's layout, which keeps every hand-off on the same
    one-hop ring because virtual stage p+1 always lives on device
    (d+1) % D).

    The tick loop executes a :class:`~repro.core.schedules.SchedulePlan`:

    * tick t issues the ring ``ppermute`` of the *previous* tick's
      output first (``ppermute_future``), runs the local cell-group
      ``lax.scan``, then forces the permute anchored on that compute —
      the collective and the scan overlap, and a value produced at tick
      t is consumed at tick t+2 (the plan's ``handoff``);
    * every source is round-robin sharded over the axis with a rotation
      offset matching its injection device, and a per-source one-item
      carousel register rotates its items into that device exactly when
      the plan consumes them — multi-source zips pipeline with no
      per-stage replication of any source;
    * only the last device writes the output buffer; it is returned
      stage-sharded and the caller slices the final stage's block — no
      collective touches the outs.

    The plan tables follow the tick-plan column contract documented in
    :mod:`repro.core.schedules` (the single normative description of
    microbatch/group/slot/feed/stash columns).

    Training backward, pluggable (``backward=``):

    * ``"autodiff"`` (default) — the schedule is data-oblivious, so
      ``jax.grad`` through it yields the reversed (backward) pipeline
      automatically: GPipe by autodiff.  Every schedule then stashes
      all ``V*M`` unit inputs per device.
    * ``"planned"`` — the backward is itself a scheduled computation:
      a ``jax.custom_vjp`` runs the combined plan's B units over the
      same one-hop ring in the reverse direction
      (:meth:`_run_chain_planned`), making ``one_f_one_b`` a real
      F/B-interleaved schedule at the plan level rather than a memory
      model.  Gradients are bitwise-equal to the autodiff path.
    """

    name = "future"

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        axis_name: str,
        schedule: str = "gpipe",
        interleave: int = 1,
        backward: str = "autodiff",
    ):
        self.mesh = mesh
        self.axis_name = axis_name
        self.schedule = schedule
        self.interleave = interleave if schedule == "interleaved" else 1
        if schedule != "interleaved" and interleave != 1:
            raise ValueError(f"{schedule=} requires interleave=1, got {interleave}")
        self.backward = validate_backward(backward)
        # Partial-manual shard_map: only the pipeline axis is manual; any
        # other mesh axes (data/model) keep automatic GSPMD partitioning,
        # so stages can themselves be FSDP×TP sharded (production mode).

    def plan_for(
        self,
        num_microbatches: int,
        inject_positions: tuple[int, ...] = (0,),
        feedback_lag: int | None = None,
    ) -> SchedulePlan:
        """The tick plan this evaluator would run for M microbatches."""
        return build_plan(
            self.schedule,
            self.mesh.shape[self.axis_name],
            num_microbatches,
            self.interleave,
            inject_positions=inject_positions,
            feedback_lag=feedback_lag,
        )

    def run_graph(self, stream: Stream) -> StreamResult:
        chain = stream.lower()
        states, outs = self._run_chain(chain)
        return StreamResult(items=outs, states=states)

    def __call__(self, program, items: PyTree = None) -> tuple[PyTree, PyTree]:
        chain, legacy = _as_chain(program, items)
        states, outs = self._run_chain(chain)
        if legacy:
            return states[0], outs
        return states, outs

    # -- chain execution ---------------------------------------------------

    def _run_chain(self, chain: G.ChainProgram) -> tuple[tuple, PyTree]:
        if self.backward == "planned" and chain.num_cells > 0:
            return self._run_chain_planned(chain)
        axis = self.axis_name
        num_devices = self.mesh.shape[axis]
        num_virtual = num_devices * self.interleave
        m_ = chain.num_items
        fb = chain.feedback

        # Segment-free program: pure data plumbing, no pipeline region.
        if chain.num_cells == 0:
            if fb is not None:
                raise ValueError(
                    "a segment-free feedback chain has nothing to "
                    "pipeline; run it with LazyEvaluator"
                )
            feeds = [inj.materialize() for inj in chain.injections]
            outs = feeds[0]
            for inj, feed in zip(chain.injections[1:], feeds[1:]):
                outs = G.apply_per_item(
                    lambda ab, _c=inj.combine: _c(*ab), (outs, feed)
                )
            if chain.finalize is not None:
                outs = G.apply_per_item(chain.finalize, outs)
            return (), outs

        if chain.num_cells % num_virtual != 0:
            raise ValueError(
                f"num_cells={chain.num_cells} not divisible by axis "
                f"'{axis}' size {num_devices} x interleave {self.interleave}"
            )
        cells_per_group = chain.num_cells // num_virtual

        # Injection layout: every zip must land on a virtual-stage
        # boundary; post-pipeline merges (cell_index == num_cells) are
        # applied outside the region.
        pipelined_inj: list[G.ChainInjection] = []
        tail_inj: list[G.ChainInjection] = []
        positions: list[int] = []
        for inj in chain.injections:
            if inj.cell_index >= chain.num_cells and inj.combine is not None:
                tail_inj.append(inj)
                continue
            if inj.cell_index % cells_per_group != 0:
                raise ValueError(
                    f"zip injection at cell {inj.cell_index} does not fall "
                    f"on a virtual-stage boundary (cells_per_group="
                    f"{cells_per_group}, D={num_devices}, "
                    f"V={self.interleave}); move the zip or change the "
                    f"stage split"
                )
            pipelined_inj.append(inj)
            positions.append(inj.cell_index // cells_per_group)

        plan = self.plan_for(
            m_, tuple(positions), feedback_lag=fb.lag if fb else None
        )
        d_, v_, k_ = num_devices, self.interleave, plan.num_slots
        n_src = len(pipelined_inj)
        entry_src = [s for s in range(n_src) if positions[s] == 0]

        # One fused chain: raw fast path for a single plain segment (the
        # common case, and bit/HLO-identical to the pre-algebra engine);
        # switch-dispatched unified state otherwise.  const_state is the
        # read-only half of the split: stage-sharded like the mutable
        # state, but delivered to the cells as scan xs only — it never
        # enters the tick carry, the idle-tick cond, or a write-back.
        cell_fn, init_state, const_state, mutable, split_states = (
            G._chain_cell_machinery(chain)
        )

        # Device-major cell layout: device d's shard holds its V groups
        # back to back (group v = cells of virtual stage v*D + d).  For
        # V == 1 this is the identity; for V > 1 it is one gather at the
        # region boundary (and its inverse on the way out).
        perm = np.concatenate(
            [
                np.arange(cells_per_group) + (v * d_ + d) * cells_per_group
                for d in range(d_)
                for v in range(v_)
            ]
        )
        inv_perm = np.argsort(perm)
        if v_ > 1:
            init_state = jax.tree.map(lambda x: x[perm], init_state)
            const_state = jax.tree.map(lambda x: x[perm], const_state)

        # Per-source round-robin feed shards: global (D, J, ...) with a
        # rotation offset so source s's item m sits on its injection
        # device exactly when the carousel has advanced m times.  A
        # feedback chain's primary source holds only its `lag` init
        # items, so the feed length is per source.
        sources = [inj.materialize() for inj in pipelined_inj]
        src_items = [
            G.leading_axis_size(src, f"source {s} items")
            for s, src in enumerate(sources)
        ]
        feeds_fed = tuple(
            jax.tree.map(
                lambda x, _o=plan.inject_devices[s], _n=src_items[s]:
                    _round_robin_feed(x, d_, _n, offset=_o),
                sources[s],
            )
            for s in range(n_src)
        )

        combines = [inj.combine for inj in pipelined_inj]
        interior_src = [s for s in range(n_src) if positions[s] != 0]

        def entry_fold(feed_items):
            flow = feed_items[0]
            for s in entry_src[1:]:
                flow = combines[s](flow, feed_items[s])
            return flow

        # Flowing item structure: what the entry zips produce (for a
        # single source, the source's own items).
        flow_shape = jax.eval_shape(
            entry_fold,
            [
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), src
                )
                for src in sources
            ],
        )
        if fb is not None:
            # A fed-back item re-enters through the same entry combines
            # as an init item, so entry zips on a feedback chain must be
            # structure-preserving overlays; emit must preserve the
            # flowing structure too (it rides the hand-off ring).
            prim_shape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                sources[0],
            )
            if not G.structures_match(prim_shape, flow_shape):
                raise ValueError(
                    "entry zips on a feedback chain must preserve the "
                    "primary item structure (the fed-back item re-enters "
                    "through the same combines)"
                )
            G._check_emit_structure(fb.emit, flow_shape)

        spec_shard = lambda tree: jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(axis), tree
        )

        fwd_ring = [(i, (i + 1) % d_) for i in range(d_)]
        rev_ring = [(i, (i - 1) % d_) for i in range(d_)]

        # Plan tables as device constants; rows are consumed as scan xs
        # so no tick indexing ever lowers to a gather.
        xs = {
            "mb": jnp.asarray(plan.microbatch),
            "grp": jnp.asarray(plan.group),
            "rslot": jnp.asarray(plan.read_slot),
            "cslot": jnp.asarray(plan.recv_slot),
            "coll": jnp.asarray(plan.collect),
            "emit": jnp.asarray(plan.emit),
            # (num_ticks, num_sources): transposed so scan slices a
            # per-tick row; the python loop over sources indexes it
            # statically.
            "src_reload": jnp.asarray(plan.src_feed_reload.T),
            "src_idx": jnp.asarray(plan.src_feed_idx.T),
            "src_adv": jnp.asarray(plan.src_feed_advance.T),
            "src_consume": jnp.asarray(plan.src_consume.T),
        }

        def pipelined(stage_ids, local_states, local_consts, local_feeds):
            stage = stage_ids[0]
            local_feeds = [
                jax.tree.map(lambda x: x[0], f) for f in local_feeds
            ]  # each (J, ...)
            feed0 = [
                jax.tree.map(lambda x: jnp.zeros_like(x[0]), f)
                for f in local_feeds
            ]
            zero_item = jax.tree.map(
                lambda x: jnp.zeros(x.shape, x.dtype), flow_shape
            )
            buf0 = jax.tree.map(
                lambda x: jnp.zeros((k_,) + x.shape, x.dtype), flow_shape
            )
            outs0 = jax.tree.map(
                lambda x: jnp.zeros((m_,) + x.shape, x.dtype), flow_shape
            )
            if v_ > 1:
                local_states = jax.tree.map(
                    lambda x: x.reshape((v_, cells_per_group) + x.shape[1:]),
                    local_states,
                )
                local_consts = jax.tree.map(
                    lambda x: x.reshape((v_, cells_per_group) + x.shape[1:]),
                    local_consts,
                )

            def group_scan(const_g, states_g, flowing):
                # One device-group = Lazy scan over its local cells: the
                # Future monad wraps whole chunks of the chain (the
                # paper's §7 grouping, applied to cells as well as items).
                # The const rows ride the xs side only: read per cell,
                # never part of the carry or the ys write-back.
                # G.scan_cell is the shared scan body — the per-cell
                # primitive sequence must match the Lazy executors'.
                out, new_states = lax.scan(
                    G.scan_cell(cell_fn, mutable), flowing,
                    (const_g, states_g),
                )
                return new_states, out

            def tick(carry, x):
                states, out_prev, feeds, buf, outs = carry
                mb = jnp.take(x["mb"], stage)
                grp = jnp.take(x["grp"], stage)
                rslot = jnp.take(x["rslot"], stage)
                cslot = jnp.take(x["cslot"], stage)
                coll = jnp.take(x["coll"], stage)

                # 1. Issue all collectives *now*; they complete while
                # this tick's cell scan runs (forced below).
                send_fut = ppermute_future(out_prev, axis, fwd_ring)
                feed_curs = []
                feed_futs = []
                for s in range(n_src):
                    fc = _tree_where(
                        x["src_reload"][s] > 0,
                        jax.tree.map(
                            lambda it: lax.dynamic_index_in_dim(
                                it, x["src_idx"][s], keepdims=False
                            ),
                            local_feeds[s],
                        ),
                        feeds[s],
                    )
                    feed_curs.append(fc)
                    feed_futs.append(ppermute_future(fc, axis, rev_ring))

                # 2. Input: a fresh injection (the entry zips' fold over
                # their feed registers), a buffered future the
                # predecessor emitted `handoff` ticks ago — which under
                # feedback is also how item b-lag's emitted output
                # re-enters at position 0 — or, at an injection device,
                # that value merged with the consuming zip's register.
                slot_val = jax.tree.map(
                    lambda b: lax.dynamic_index_in_dim(
                        b, jnp.clip(rslot, 0, k_ - 1), keepdims=False
                    ),
                    buf,
                )
                if fb is None:
                    inp = _tree_where(rslot < 0, entry_fold(feed_curs), slot_val)
                else:
                    # Entry zips gate on their consume column so they
                    # overlay fed-back entries (rslot >= 0) as well as
                    # fresh init items — the carousel admitting new
                    # requests into retired slots mid-flight.
                    inp = _tree_where(rslot < 0, feed_curs[0], slot_val)
                    for s in entry_src[1:]:
                        merged = combines[s](inp, feed_curs[s])
                        apply_s = (x["src_consume"][s] > 0) & (
                            stage == plan.inject_devices[s]
                        )
                        inp = _tree_where(apply_s, merged, inp)
                for s in interior_src:
                    merged = combines[s](inp, feed_curs[s])
                    apply_s = (x["src_consume"][s] > 0) & (
                        stage == plan.inject_devices[s]
                    )
                    inp = _tree_where(apply_s, merged, inp)

                # 3. Advance mb through this tick's cell group.
                if v_ > 1:
                    states_g = jax.tree.map(
                        lambda s: lax.dynamic_index_in_dim(
                            s, grp, keepdims=False
                        ),
                        states,
                    )
                    const_g = jax.tree.map(
                        lambda s: lax.dynamic_index_in_dim(
                            s, grp, keepdims=False
                        ),
                        local_consts,
                    )
                else:
                    states_g = states
                    const_g = local_consts
                valid = mb >= 0
                if mutable:
                    # Idle ticks (fill/drain) skip the cell scan *and*
                    # the state write-back entirely: a whole-state
                    # where(valid, new, old) would copy every cache
                    # byte per tick — the dominant cost of a serving
                    # chain whose state is the KV cache.  Invalid-tick
                    # outputs are never collected, stored, or read, so
                    # passing the input through is unobservable.  The
                    # const rows are a closure capture of the taken
                    # branch, not a cond output — read-only state is
                    # structurally exempt from the write-back.
                    new_sg, out = lax.cond(
                        valid,
                        lambda args: group_scan(const_g, *args),
                        lambda args: args,
                        (states_g, inp),
                    )
                else:
                    new_sg, out = group_scan(const_g, states_g, inp)
                if fb is not None:
                    # Final virtual stage: the emitted item is both the
                    # collected output and — one ring hop later — the
                    # entry input of item mb + lag.  The plan's emit
                    # column (last-stage-only by construction) keys the
                    # sole region containing the LM head: every other
                    # device's tick body never takes this branch, and
                    # the HLO keeps the head matmul conditional-guarded
                    # (asserted in the serving tests).
                    emit_here = jnp.take(x["emit"], stage)
                    out = lax.cond(emit_here > 0, fb.emit, lambda o: o, out)
                if mutable:
                    if v_ > 1:
                        states = jax.tree.map(
                            lambda s, g: lax.dynamic_update_index_in_dim(
                                s, g, grp, 0
                            ),
                            states,
                            new_sg,
                        )
                    else:
                        states = new_sg

                # 4. Last virtual stage: materialize the result locally.
                # Masked row-level dynamic update (not where(o.at[].set))
                # so XLA can update the scan carry in place instead of
                # copying the whole outs buffer every tick.
                write = valid & (coll > 0)
                idx = jnp.clip(mb, 0, m_ - 1)
                outs = jax.tree.map(
                    lambda o, v: lax.dynamic_update_index_in_dim(
                        o,
                        jnp.where(
                            write,
                            v,
                            lax.dynamic_index_in_dim(o, idx, keepdims=False),
                        ),
                        idx,
                        0,
                    ),
                    outs,
                    out,
                )

                # 5. Force the futures, anchored on the compute they
                # overlapped; store the arrival in its planned slot.
                arrived = send_fut.force(anchor=out)
                slot = jnp.clip(cslot, 0, k_ - 1)
                buf = jax.tree.map(
                    lambda b, a: lax.dynamic_update_index_in_dim(
                        b,
                        jnp.where(
                            cslot >= 0,
                            a,
                            lax.dynamic_index_in_dim(b, slot, keepdims=False),
                        ),
                        slot,
                        0,
                    ),
                    buf,
                    arrived,
                )
                new_feeds = tuple(
                    _tree_where(
                        x["src_adv"][s] > 0,
                        feed_futs[s].force(anchor=out),
                        feed_curs[s],
                    )
                    for s in range(n_src)
                )
                return (states, out, new_feeds, buf, outs), None

            carry0 = (local_states, zero_item, tuple(feed0), buf0, outs0)
            (local_states, _, _, _, outs), _ = lax.scan(tick, carry0, xs)
            if v_ > 1:
                local_states = jax.tree.map(
                    lambda x: x.reshape((v_ * cells_per_group,) + x.shape[2:]),
                    local_states,
                )
            return local_states, outs

        pipelined = jax.shard_map(
            pipelined,
            mesh=self.mesh,
            in_specs=(
                jax.sharding.PartitionSpec(axis),
                spec_shard(init_state),
                spec_shard(const_state),
                tuple(spec_shard(f) for f in feeds_fed),
            ),
            out_specs=(spec_shard(init_state), spec_shard(flow_shape)),
            axis_names={axis},
            check_vma=_CHECK_VMA,
        )
        final_states, outs = pipelined(
            jnp.arange(d_, dtype=jnp.int32), init_state, const_state, feeds_fed
        )
        if v_ > 1:
            # Back to chain order, still split over the stages (left to
            # itself the gather replicates the state on every device).
            staged = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(axis)
            )
            final_states = jax.tree.map(
                lambda x: lax.with_sharding_constraint(x[inv_perm], staged),
                final_states,
            )
        # outs is stage-sharded (D*M, ...); only the last stage's block is
        # real.  One static slice at the boundary — no psum, no all-reduce.
        outs = jax.tree.map(
            lambda o: lax.slice_in_dim(o, (d_ - 1) * m_, d_ * m_, axis=0),
            outs,
        )
        # Post-pipeline merges (zips past the last cell) and fused tail
        # maps apply per item outside the region.
        for inj in tail_inj:
            outs = G.apply_per_item(
                lambda ab, _c=inj.combine: _c(*ab), (outs, inj.materialize())
            )
        if chain.finalize is not None:
            outs = G.apply_per_item(chain.finalize, outs)
        return split_states(final_states), outs

    # -- planned backward (true 1F1B custom-VJP) ---------------------------

    def _run_chain_planned(self, chain: G.ChainProgram) -> tuple[tuple, PyTree]:
        """Execute the chain with the backward pass as scheduled B units.

        The combined plan (:func:`repro.core.schedules.build_combined_plan`)
        is the schedule artifact; this method realizes it under XLA's
        two-phase autodiff protocol with ``jax.custom_vjp``:

        * **fwd** runs the plan's F units (the ordinary forward tick
          scan) and additionally stashes every unit's input activation
          into per-device stash buffers (slot ``group * M + m`` — the
          phase-split coloring; see :class:`~repro.core.schedules.
          CombinedPlan` for why the boundary forces all ``V*M`` live).
        * **bwd** replays the plan's B units in combined-plan order
          (:func:`~repro.core.schedules.build_backward_plan` — the
          mirrored tables): cotangent seeds ``d_out[m]`` ride a flipped
          feed carousel into device D-1, each B unit re-linearizes its
          cell group at the stashed input (``jax.vjp`` — group-level
          rematerialization, so ``remat`` is moot here) and the produced
          input-cotangent rides :func:`~repro.core.future.
          ppermute_future` one hop down the *reverse* ring, overlapping
          the next unit's transpose exactly as the forward overlaps its
          sends.  Entry units emit the source-item gradients on device 0.

        Weight-gradient contributions are staged per (group, m) and
        reduced in reverse forward-tick order (m descending per group) —
        the order ``jax.grad`` of the forward plan accumulates in — so
        planned gradients are *bitwise* equal to the autodiff path
        (tested across the schedule zoo).  The staging buffer is M× the
        stage weight-grad footprint; the ZB-H1 W-unit split (plan
        groundwork shipped) is the path to folding it away.

        Constraints (clear errors otherwise): single-source chains,
        immutable cell state (1F1B's B-unit order ``m = 0..M-1`` is
        only sound when cells never mutate state across items — a
        mutable chain's transpose needs ``m`` *descending*), floating
        point items, no feedback.
        """
        axis = self.axis_name
        d_ = self.mesh.shape[axis]
        v_ = self.interleave
        num_virtual = d_ * v_
        m_ = chain.num_items

        if chain.feedback is not None:
            raise ValueError(
                "backward='planned' does not support feedback chains "
                "(decode loops do not train); use backward='autodiff'"
            )
        if len(chain.injections) != 1:
            raise ValueError(
                "backward='planned' supports single-source chains only "
                "(the training shape: one stream of microbatches); use "
                "backward='autodiff' for zip/multi-source programs"
            )
        if chain.num_cells % num_virtual != 0:
            raise ValueError(
                f"num_cells={chain.num_cells} not divisible by axis "
                f"'{axis}' size {d_} x interleave {v_}"
            )
        cells_per_group = chain.num_cells // num_virtual

        cell_fn, init_state, const_state, mutable, split_states = (
            G._chain_cell_machinery(chain)
        )
        if mutable:
            raise ValueError(
                "backward='planned' requires immutable cell state "
                "(mutable_state=False): the 1F1B backward runs items in "
                "ascending order, which is only a valid transpose when "
                "cells do not mutate state across items; use "
                "backward='autodiff'"
            )
        if const_state is not None:
            raise ValueError(
                "backward='planned' does not support const_state segments "
                "(const leaves are excluded from differentiation by "
                "construction); put read-only differentiable state in an "
                "ordinary mutable_state=False segment, or use "
                "backward='autodiff'"
            )
        cell_fn = lambda st, it, _f=cell_fn: _f(None, st, it)

        src = chain.injections[0].materialize()
        for leaf in jax.tree.leaves(src):
            if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                raise ValueError(
                    "backward='planned' requires floating-point source "
                    "items (cotangents ride the same ring buffers)"
                )
        G.leading_axis_size(src, "items")

        # Differentiate only the inexact state leaves: the unified
        # multi-segment machinery threads integer bookkeeping (cell /
        # segment indices) through the state, whose cotangents are
        # symbolic float0 — they never ride the ring.
        state_leaves, state_def = jax.tree.flatten(init_state)
        diff_ids = tuple(
            i
            for i, leaf in enumerate(state_leaves)
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)
        )

        plan = self.plan_for(m_)
        bplan = build_backward_plan(
            self.schedule, d_, m_, v_, plan.handoff
        )
        k_, kb_ = plan.num_slots, bplan.num_slots
        n_stash = v_ * m_

        perm = np.concatenate(
            [
                np.arange(cells_per_group) + (v * d_ + d) * cells_per_group
                for d in range(d_)
                for v in range(v_)
            ]
        )
        inv_perm = np.argsort(perm)

        item_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), src
        )
        spec_shard = lambda tree: jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(axis), tree
        )
        fwd_ring = [(i, (i + 1) % d_) for i in range(d_)]
        rev_ring = [(i, (i - 1) % d_) for i in range(d_)]

        def _plan_xs(p: SchedulePlan):
            return {
                "mb": jnp.asarray(p.microbatch),
                "grp": jnp.asarray(p.group),
                "rslot": jnp.asarray(p.read_slot),
                "cslot": jnp.asarray(p.recv_slot),
                "coll": jnp.asarray(p.collect),
                "reload": jnp.asarray(p.feed_reload),
                "idx": jnp.asarray(p.feed_idx),
                "adv": jnp.asarray(p.feed_advance),
            }

        xs_f, xs_b = _plan_xs(plan), _plan_xs(bplan)

        def _zeros(shape_prefix, struct):
            return jax.tree.map(
                lambda s: jnp.zeros(shape_prefix + s.shape, s.dtype),
                struct,
            )

        def _row_update(buf, row, idx, write):
            """Masked row write that XLA can do in place (see the outs
            write in the forward engine)."""
            return jax.tree.map(
                lambda b, v: lax.dynamic_update_index_in_dim(
                    b,
                    jnp.where(
                        write,
                        v,
                        lax.dynamic_index_in_dim(b, idx, keepdims=False),
                    ),
                    idx,
                    0,
                ),
                buf,
                row,
            )

        def group_apply(states_g, flowing):
            # Same per-cell primitive sequence as the forward engine's
            # group_scan (bit-equality of outputs and of their vjp).
            def cell(fl, st):
                _st, out = cell_fn(st, fl)
                return out, None

            out, _ = lax.scan(cell, flowing, states_g)
            return out

        def _state_groups(local_states):
            # (V, cells_per_group, ...) local view; V == 1 is group 0.
            return jax.tree.map(
                lambda x: x.reshape((v_, cells_per_group) + x.shape[1:]),
                local_states,
            )

        # -- fwd phase: the forward plan's F units (+ activation stash) ----
        def _make_forward(with_stash: bool):
            """The forward tick scan.  The stash buffer (the planned
            backward's residuals) threads through the scan carry only
            when a VJP will consume it: the primal-only path (forward
            evaluation without jax.grad) must not pay a per-tick
            whole-buffer stash write — the same masked-carry copy cost
            the serving engine's cond-gating exists to avoid."""

            def forward_region(stage_ids, local_states, local_feed):
                stage = stage_ids[0]
                local_feed = jax.tree.map(lambda x: x[0], local_feed)
                states_v = _state_groups(local_states)
                carry0 = (
                    _zeros((), item_struct),      # out_prev
                    _zeros((), jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                        local_feed,
                    )),                            # feed register
                    _zeros((k_,), item_struct),    # in-flight hand-offs
                    _zeros((m_,), item_struct),    # outs
                )
                if with_stash:
                    carry0 += (_zeros((n_stash,), item_struct),)

                def tick(carry, x):
                    out_prev, feed_reg, buf, outs = carry[:4]
                    mb = jnp.take(x["mb"], stage)
                    grp = jnp.take(x["grp"], stage)
                    rslot = jnp.take(x["rslot"], stage)
                    cslot = jnp.take(x["cslot"], stage)
                    coll = jnp.take(x["coll"], stage)

                    send_fut = ppermute_future(out_prev, axis, fwd_ring)
                    fc = _tree_where(
                        x["reload"] > 0,
                        jax.tree.map(
                            lambda it: lax.dynamic_index_in_dim(
                                it, x["idx"], keepdims=False
                            ),
                            local_feed,
                        ),
                        feed_reg,
                    )
                    feed_fut = ppermute_future(fc, axis, rev_ring)

                    slot_val = jax.tree.map(
                        lambda b: lax.dynamic_index_in_dim(
                            b, jnp.clip(rslot, 0, k_ - 1), keepdims=False
                        ),
                        buf,
                    )
                    inp = _tree_where(rslot < 0, fc, slot_val)
                    states_g = jax.tree.map(
                        lambda s: lax.dynamic_index_in_dim(
                            s, grp, keepdims=False
                        ),
                        states_v,
                    )
                    out = group_apply(states_g, inp)

                    valid = mb >= 0
                    outs = _row_update(
                        outs, out, jnp.clip(mb, 0, m_ - 1), valid & (coll > 0)
                    )
                    if with_stash:
                        sslot = jnp.clip(grp * m_ + mb, 0, n_stash - 1)
                        stash = _row_update(carry[4], inp, sslot, valid)

                    arrived = send_fut.force(anchor=out)
                    buf = _row_update(
                        buf, arrived, jnp.clip(cslot, 0, k_ - 1), cslot >= 0
                    )
                    feed_reg = _tree_where(
                        x["adv"] > 0, feed_fut.force(anchor=out), fc
                    )
                    carry_out = (out, feed_reg, buf, outs)
                    if with_stash:
                        carry_out += (stash,)
                    return carry_out, None

                final, _ = lax.scan(tick, carry0, xs_f)
                outs = final[3]
                if with_stash:
                    return outs, final[4]
                return outs

            out_specs = (
                (spec_shard(item_struct), spec_shard(item_struct))
                if with_stash
                else spec_shard(item_struct)
            )
            region = jax.shard_map(
                forward_region,
                mesh=self.mesh,
                in_specs=(
                    jax.sharding.PartitionSpec(axis),
                    spec_shard(init_state),
                    spec_shard(item_struct),
                ),
                out_specs=out_specs,
                axis_names={axis},
                check_vma=_CHECK_VMA,
            )

            def forward(state0, src_items):
                state_p = (
                    jax.tree.map(lambda x: x[perm], state0)
                    if v_ > 1
                    else state0
                )
                feed = jax.tree.map(
                    lambda x: _round_robin_feed(x, d_, m_), src_items
                )
                res = region(jnp.arange(d_, dtype=jnp.int32), state_p, feed)
                outs, stash = res if with_stash else (res, None)
                outs = jax.tree.map(
                    lambda o: lax.slice_in_dim(
                        o, (d_ - 1) * m_, d_ * m_, axis=0
                    ),
                    outs,
                )
                return outs, stash

            return forward

        _forward_primal = _make_forward(False)
        _forward = _make_forward(True)

        # -- bwd phase: the combined plan's B units over the reverse ring --
        def backward_region(stage_ids, local_states, local_stash,
                            local_dfeed, local_dfinal_diff):
            stage = stage_ids[0]
            local_dfeed = jax.tree.map(lambda x: x[0], local_dfeed)
            states_v = _state_groups(local_states)
            states_v_leaves = jax.tree.leaves(states_v)
            group_diff_struct = tuple(
                jax.ShapeDtypeStruct(
                    states_v_leaves[i].shape[1:], states_v_leaves[i].dtype
                )
                for i in diff_ids
            )
            zero_item = _zeros((), item_struct)
            carry0 = (
                zero_item,                          # cotangent being sent
                _zeros((), jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                    local_dfeed,
                )),                                  # d_out seed register
                _zeros((kb_,), item_struct),         # in-flight cotangents
                _zeros((n_stash,), group_diff_struct),  # staged dW (grp, m)
                _zeros((m_,), item_struct),          # d_items (device 0)
            )

            def tick(carry, x):
                dflow_prev, dfeed_reg, dbuf, staging, ditems = carry
                mb = jnp.take(x["mb"], stage)
                grp = jnp.take(x["grp"], stage)
                rslot = jnp.take(x["rslot"], stage)
                cslot = jnp.take(x["cslot"], stage)
                coll = jnp.take(x["coll"], stage)

                send_fut = ppermute_future(dflow_prev, axis, rev_ring)
                fc = _tree_where(
                    x["reload"] > 0,
                    jax.tree.map(
                        lambda it: lax.dynamic_index_in_dim(
                            it, x["idx"], keepdims=False
                        ),
                        local_dfeed,
                    ),
                    dfeed_reg,
                )
                feed_fut = ppermute_future(fc, axis, fwd_ring)

                slot_val = jax.tree.map(
                    lambda b: lax.dynamic_index_in_dim(
                        b, jnp.clip(rslot, 0, kb_ - 1), keepdims=False
                    ),
                    dbuf,
                )
                g = _tree_where(rslot < 0, fc, slot_val)
                valid = mb >= 0
                sslot = jnp.clip(grp * m_ + mb, 0, n_stash - 1)
                xin = jax.tree.map(
                    lambda s: lax.dynamic_index_in_dim(
                        s, sslot, keepdims=False
                    ),
                    local_stash,
                )
                states_g = jax.tree.map(
                    lambda s: lax.dynamic_index_in_dim(s, grp, keepdims=False),
                    states_v,
                )
                sg_leaves = jax.tree.leaves(states_g)
                sg_def = jax.tree.structure(states_g)
                diff_vals = tuple(sg_leaves[i] for i in diff_ids)

                def apply_diff(diff_vals_, x_):
                    full = list(sg_leaves)
                    for i, val in zip(diff_ids, diff_vals_):
                        full[i] = val
                    return group_apply(jax.tree.unflatten(sg_def, full), x_)

                def unit(args):
                    dv_, x_, g_ = args
                    _out, vjp_fn = jax.vjp(apply_diff, dv_, x_)
                    return vjp_fn(g_)

                def idle(args):
                    dv_, x_, _g = args
                    return (
                        tuple(jnp.zeros_like(v) for v in dv_),
                        jax.tree.map(jnp.zeros_like, x_),
                    )

                dsg, dx = lax.cond(valid, unit, idle, (diff_vals, xin, g))
                staging = _row_update(staging, dsg, sslot, valid)
                ditems = _row_update(
                    ditems, dx, jnp.clip(mb, 0, m_ - 1), valid & (coll > 0)
                )

                arrived = send_fut.force(anchor=dx)
                dbuf = _row_update(
                    dbuf, arrived, jnp.clip(cslot, 0, kb_ - 1), cslot >= 0
                )
                dfeed_reg = _tree_where(
                    x["adv"] > 0, feed_fut.force(anchor=dx), fc
                )
                return (dx, dfeed_reg, dbuf, staging, ditems), None

            (_, _, _, staging, ditems), _ = lax.scan(tick, carry0, xs_b)

            # Weight-grad reduction in the order jax.grad of the forward
            # plan accumulates: per group, microbatch M-1 down to 0,
            # seeded with the final-states cotangent (bitwise parity).
            staging_v = jax.tree.map(
                lambda s: s.reshape((v_, m_) + s.shape[1:]), staging
            )
            dfinal_v = tuple(
                x.reshape((v_, cells_per_group) + x.shape[1:])
                for x in local_dfinal_diff
            )

            def reduce_step(acc, i):
                acc = jax.tree.map(
                    lambda a, s: a
                    + lax.dynamic_index_in_dim(
                        s, m_ - 1 - i, axis=1, keepdims=False
                    ),
                    acc,
                    staging_v,
                )
                return acc, None

            dstates_v, _ = lax.scan(
                reduce_step, dfinal_v, jnp.arange(m_, dtype=jnp.int32)
            )
            dstates_diff = jax.tree.map(
                lambda x: x.reshape((v_ * cells_per_group,) + x.shape[2:]),
                dstates_v,
            )
            return dstates_diff, ditems

        diff_struct = tuple(
            jax.ShapeDtypeStruct(state_leaves[i].shape, state_leaves[i].dtype)
            for i in diff_ids
        )
        backward_region = jax.shard_map(
            backward_region,
            mesh=self.mesh,
            in_specs=(
                jax.sharding.PartitionSpec(axis),
                spec_shard(init_state),
                spec_shard(item_struct),
                spec_shard(item_struct),
                spec_shard(diff_struct),
            ),
            out_specs=(spec_shard(diff_struct), spec_shard(item_struct)),
            axis_names={axis},
            check_vma=_CHECK_VMA,
        )

        def _backward(state0, stash, d_final_diff, d_outs):
            state_p = (
                jax.tree.map(lambda x: x[perm], state0) if v_ > 1 else state0
            )
            dfinal_p = (
                tuple(x[perm] for x in d_final_diff)
                if v_ > 1
                else tuple(d_final_diff)
            )
            dfeed = jax.tree.map(
                lambda x: _round_robin_feed(x, d_, m_, flip=True), d_outs
            )
            dstates_diff, ditems = backward_region(
                jnp.arange(d_, dtype=jnp.int32), state_p, stash, dfeed,
                dfinal_p,
            )
            if v_ > 1:
                dstates_diff = tuple(x[inv_perm] for x in dstates_diff)
            ditems = jax.tree.map(
                lambda o: lax.slice_in_dim(o, 0, m_, axis=0), ditems
            )
            # Reassemble the full state cotangent: integer bookkeeping
            # leaves get symbolic float0 zeros (the custom_vjp contract).
            out_leaves: list = []
            it = iter(dstates_diff)
            for i, leaf in enumerate(state_leaves):
                if i in diff_ids:
                    out_leaves.append(next(it))
                else:
                    out_leaves.append(
                        np.zeros(np.shape(leaf), jax.dtypes.float0)
                    )
            return jax.tree.unflatten(state_def, out_leaves), ditems

        @jax.custom_vjp
        def run(state0, src_items):
            # Primal-only (no differentiation): the stash-free forward.
            outs, _ = _forward_primal(state0, src_items)
            return state0, outs

        def run_fwd(state0, src_items):
            outs, stash = _forward(state0, src_items)
            return (state0, outs), (state0, stash)

        def run_bwd(res, cot):
            state0, stash = res
            d_final, d_outs = cot
            d_final_diff = tuple(
                leaf
                for i, leaf in enumerate(jax.tree.leaves(d_final))
                if i in diff_ids
            )
            return _backward(state0, stash, d_final_diff, d_outs)

        run.defvjp(run_fwd, run_bwd)
        final_states, outs = run(init_state, src)
        if chain.finalize is not None:
            outs = G.apply_per_item(chain.finalize, outs)
        return split_states(final_states), outs


def evaluate(
    program,
    items: PyTree = None,
    evaluator: LazyEvaluator | FutureEvaluator | None = None,
) -> tuple[PyTree, PyTree]:
    """Monad-substitution entry point: same program, pluggable evaluator.

    ``program`` is a :class:`Stream` (preferred; carries its own sources)
    or a deprecated :class:`StreamProgram` with ``items``.
    """
    evaluator = evaluator or LazyEvaluator()
    return evaluator(program, items)
