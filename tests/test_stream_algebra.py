"""Stream combinator algebra: laws, IR shape, lowering, validation.

Single-device tests — Lazy ≡ Future bit-equality for every combinator on
every schedule runs in the multidevice battery (test_multidevice.py).
"""
import pytest

from _hypothesis_stub import hypothesis, st  # skips @given tests offline
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LazyEvaluator, Stream, StreamProgram, evaluate
from repro.core import graph as G


def _items(m=6, w=3, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(m, w)).astype(np.float32)
    )


def _count_cell(state, item):
    return state + 1, item * 1.5 + state.astype(jnp.float32)


class TestMapFusion:
    def test_map_map_builds_one_node(self):
        f = lambda x: x * 2.0
        g = lambda x: x + 1.0
        items = _items()
        fused = Stream.source(items).map(f).map(g)
        direct = Stream.source(items).map(lambda x: g(f(x)))
        assert len(fused.nodes()) == len(direct.nodes()) == 2
        assert sum(isinstance(n, G.MapNode) for n in fused.nodes()) == 1

    def test_map_map_values_equal(self):
        f = lambda x: x * 2.0
        g = lambda x: jnp.tanh(x)
        items = _items()
        a = Stream.source(items).map(f).map(g).collect().items
        b = Stream.source(items).map(lambda x: g(f(x))).collect().items
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @hypothesis.given(st.integers(1, 5))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_map_chain_always_one_node(self, n):
        s = Stream.source(_items())
        for i in range(n):
            s = s.map(lambda x, _i=i: x + float(_i))
        assert sum(isinstance(nd, G.MapNode) for nd in s.nodes()) == 1

    def test_map_fuses_into_segment_lowering(self):
        """A spine map leaves no standalone stage: one fused segment."""
        s = (
            Stream.source(_items())
            .map(lambda x: x * 2.0)
            .through(_count_cell, jnp.arange(4, dtype=jnp.int32))
            .map(lambda x: x + 1.0)
        )
        chain = s.lower()
        assert len(chain.segments) == 1
        assert chain.num_cells == 4
        assert chain.finalize is not None  # the tail map


class TestConcatAssociativity:
    def test_ir_shape_identical(self):
        a, b, c = (Stream.source(_items(seed=i)) for i in range(3))
        left = a.concat(b).concat(c)
        a2, b2, c2 = (Stream.source(_items(seed=i)) for i in range(3))
        right = a2.concat(b2.concat(c2))
        count = lambda s: sum(isinstance(n, G.ConcatNode) for n in s.nodes())
        assert count(left) == count(right) == 2

    def test_values_bit_equal(self):
        xs = [_items(seed=i) for i in range(3)]
        left = (
            Stream.source(xs[0]).concat(Stream.source(xs[1])).concat(Stream.source(xs[2]))
        )
        right = Stream.source(xs[0]).concat(
            Stream.source(xs[1]).concat(Stream.source(xs[2]))
        )
        np.testing.assert_array_equal(
            np.asarray(left.collect().items), np.asarray(right.collect().items)
        )

    def test_concat_lengths_add(self):
        s = Stream.source(_items(4)).concat(Stream.source(_items(3)))
        assert s.num_items == 7

    def test_concat_structure_mismatch_raises_at_construction(self):
        a = Stream.source({"x": _items()})
        b = Stream.source({"y": _items()})
        with pytest.raises(ValueError, match="structure"):
            a.concat(b)
        # masked sources also have statically known structure
        with pytest.raises(ValueError, match="structure"):
            a.mask(lambda i: i["x"] > 0).concat(b)

    def test_concat_structure_mismatch_raises_after_map_at_eval(self):
        # a map's output structure is unknowable at construction; the
        # check falls back to eval time with the same error either path
        a = Stream.source(_items()).map(lambda i: {"x": i})
        b = Stream.source({"y": _items()})
        s = a.concat(b)
        with pytest.raises(ValueError, match="structure"):
            s.collect()


class TestZipDeterminism:
    def test_source_order_not_arrival_order(self):
        """Item b of x.zip(y, f) is f(x[b], y[b]) — a pure function of the
        sources, so swapping the zip's sides with a flipped combine is
        the identical program."""
        x, y = _items(seed=1), _items(seed=2)
        ab = Stream.source(x).zip(Stream.source(y), lambda a, b: (a, b))
        ba = Stream.source(y).zip(Stream.source(x), lambda b, a: (a, b))
        ra, rb = ab.collect().items, ba.collect().items
        for u, v in zip(jax.tree.leaves(ra), jax.tree.leaves(rb)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_repeated_runs_identical(self):
        x, y = _items(seed=1), _items(seed=2)
        s = Stream.source(x).zip(Stream.source(y), lambda a, b: a * b + a)
        r1 = s.collect().items
        r2 = s.collect().items
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))

    def test_zip_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="equal stream lengths"):
            Stream.source(_items(4)).zip(
                Stream.source(_items(5)), lambda a, b: a
            )

    def test_structure_changing_mid_spine_mask_raises_clearly(self):
        """A mask between two segments changes the flowing structure; the
        pipelined executor cannot run it (ring buffers are shape-static)
        and must say so, not die in a lax.cond type mismatch."""
        w = jnp.arange(2, dtype=jnp.int32)
        masked_cell = lambda s, i: (
            s + 1,
            {"value": i["value"] * 1.5, "valid": i["valid"]},
        )
        s = (
            Stream.source(_items())
            .through(_count_cell, w)
            .mask(lambda i: i > 0.0)
            .through(masked_cell, w)
        )
        out = s.collect(LazyEvaluator()).items  # general DAG: fine
        assert out["value"].shape == (6, 3)
        chain = s.lower()
        uni = G.unify_segments(chain.segments)
        row0 = jax.tree.map(lambda l: l[0], uni.init_state)
        with pytest.raises(ValueError, match="LazyEvaluator"):
            # canonical 3-arg cell: const row (None here), state row, item
            uni.cell_fn(None, row0, _items()[0])

    def test_zip_of_stateful_pipelines_runs_lazy_but_not_chain(self):
        w = jnp.arange(2, dtype=jnp.int32)
        left = Stream.source(_items()).through(_count_cell, w)
        right = Stream.source(_items(seed=5)).through(_count_cell, w)
        z = left.zip(right, lambda a, b: a + b)
        out = z.collect(LazyEvaluator()).items  # general DAG: fine
        assert out.shape == (6, 3)
        with pytest.raises(ValueError, match="LazyEvaluator"):
            z.lower()


class TestMask:
    def test_mask_tags_validity(self):
        vals = jnp.arange(6.0)
        out = Stream.source(vals).mask(lambda v: v > 2.5).collect().items
        np.testing.assert_array_equal(
            np.asarray(out["valid"]), np.arange(6) > 2.5
        )
        np.testing.assert_array_equal(np.asarray(out["value"]), np.arange(6.0))


class TestThroughComposition:
    def test_two_segments_match_one(self):
        """Chained .through segments ≡ one longer chain (same cells)."""
        w = jnp.arange(6, dtype=jnp.int32)
        items = _items()
        one = Stream.source(items).through(_count_cell, w)
        two = (
            Stream.source(items)
            .through(_count_cell, w[:3])
            .through(_count_cell, w[3:])
        )
        r1, r2 = one.collect(), two.collect()
        np.testing.assert_array_equal(np.asarray(r1.items), np.asarray(r2.items))
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate([r2.states[0], r2.states[1]])),
            np.asarray(r1.states[0]),
        )

    def test_num_cells_inferred(self):
        s = Stream.source(_items()).through(_count_cell, jnp.zeros(5, jnp.int32))
        assert s.num_cells == 5

    def test_state_shape_mismatch_raises(self):
        with pytest.raises(ValueError, match="num_cells"):
            Stream.source(_items()).through(
                _count_cell, jnp.zeros(5, jnp.int32), num_cells=4
            )


class TestInputValidation:
    """Satellite: evaluators must reject malformed item pytrees loudly."""

    def test_empty_pytree_raises(self):
        prog = StreamProgram(_count_cell, jnp.zeros(2, jnp.int32), 2)
        with pytest.raises(ValueError, match="empty pytree"):
            evaluate(prog, {}, LazyEvaluator())

    def test_mismatched_leading_axes_raise(self):
        prog = StreamProgram(_count_cell, jnp.zeros(2, jnp.int32), 2)
        bad = {"a": jnp.zeros((4, 2)), "b": jnp.zeros((5, 2))}
        with pytest.raises(ValueError, match="leading"):
            evaluate(prog, bad, LazyEvaluator())

    def test_source_validates_too(self):
        with pytest.raises(ValueError, match="leading"):
            Stream.source({"a": jnp.zeros((4, 2)), "b": jnp.zeros((5, 2))})
        with pytest.raises(ValueError, match="empty pytree"):
            Stream.source({})

    def test_scalar_leaf_raises(self):
        with pytest.raises(ValueError, match="leading stream axis"):
            Stream.source(jnp.float32(1.0))

    def test_stream_with_items_arg_raises(self):
        s = Stream.source(_items())
        with pytest.raises(ValueError, match="its own sources"):
            evaluate(s, _items(), LazyEvaluator())


class TestFromProgram:
    def test_adapter_equivalence_and_deprecation(self):
        prog = StreamProgram(_count_cell, jnp.arange(4, dtype=jnp.int32), 4)
        items = _items()
        st_legacy, out_legacy = evaluate(prog, items, LazyEvaluator())
        with pytest.warns(DeprecationWarning, match="from_program"):
            res = Stream.from_program(prog, items).collect()
        np.testing.assert_array_equal(np.asarray(out_legacy), np.asarray(res.items))
        np.testing.assert_array_equal(
            np.asarray(st_legacy), np.asarray(res.states[0])
        )

    def test_legacy_evaluate_path_does_not_warn(self):
        """The StreamProgram adapter inside evaluate() builds the graph
        directly — deprecation fires only on explicit from_program use."""
        import warnings

        prog = StreamProgram(_count_cell, jnp.arange(4, dtype=jnp.int32), 4)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            evaluate(prog, _items(), LazyEvaluator())

    def test_adapter_forwards_program_options(self):
        """mutable_state/remat/num_cells survive the adapter — the
        lowered segment must be indistinguishable from a direct
        .through() build."""
        prog = StreamProgram(
            lambda w, x: (w, x * w[0]), jnp.arange(1.0, 4.0).reshape(3, 1), 3,
            mutable_state=False, remat=True,
        )
        with pytest.warns(DeprecationWarning):
            stream = Stream.from_program(prog, _items())
        seg = stream.lower().segments[0]
        assert seg.num_cells == 3
        assert seg.mutable_state is False
        assert seg.remat is True

    def test_adapter_grad_matches_direct_build(self):
        """jax.grad through the adapter equals the direct algebra build
        bitwise (the adapter adds no ops)."""
        w0 = jnp.linspace(0.2, 0.8, 3)
        items = _items()

        def cell(w, x):
            return w, jnp.tanh(x * w)

        def loss_adapter(w):
            import warnings

            prog = StreamProgram(cell, w, 3, mutable_state=False)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                res = Stream.from_program(prog, items).collect()
            return jnp.sum(res.items ** 2)

        def loss_direct(w):
            res = (
                Stream.source(items)
                .through(cell, w, mutable_state=False)
                .collect()
            )
            return jnp.sum(res.items ** 2)

        ga = jax.grad(loss_adapter)(w0)
        gd = jax.grad(loss_direct)(w0)
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gd))


class TestFeedback:
    """The unfold combinator: item b >= lag is emit(out[b - lag])."""

    def _emit(self, item):
        return item * 0.5 + 1.0

    def _reference(self, init, n, states0, emit):
        from jax import lax

        lag = init.shape[0]

        def run_item(states, flow):
            def c(fl, s):
                ns, out = _count_cell(s, fl)
                return out, ns

            out, ns = lax.scan(c, flow, states)
            return ns, out

        ring = [init[i] for i in range(lag)]
        states, outs = states0, []
        for b in range(n):
            inp = ring.pop(0) if b < lag else outs[b - lag]
            states, raw = run_item(states, inp)
            outs.append(emit(raw))
        return jnp.stack(outs), states

    @pytest.mark.parametrize("lag,n", [(1, 5), (3, 14), (4, 4)])
    def test_lazy_matches_unrolled_reference(self, lag, n):
        init = jnp.asarray(
            np.random.default_rng(1).normal(size=(lag, 3)).astype(np.float32)
        )
        states0 = jnp.arange(4, dtype=jnp.int32)
        res = (
            Stream.feedback(init, n, self._emit)
            .through(_count_cell, states0)
            .collect(LazyEvaluator())
        )
        ref_items, ref_states = self._reference(init, n, states0, self._emit)
        np.testing.assert_allclose(
            np.asarray(res.items), np.asarray(ref_items), rtol=1e-6
        )
        np.testing.assert_array_equal(
            np.asarray(res.states[0]), np.asarray(ref_states)
        )

    def test_entry_zip_overlay(self):
        """An entry zip merges into fed-back items too (the admission
        overlay): items where the source gates are replaced wholesale,
        so their outputs depend only on the overlay value."""
        from jax import lax

        lag, n = 2, 8
        init = jnp.ones((lag, 3))
        overlay = jnp.where(
            (jnp.arange(n) % 3 == 0)[:, None], jnp.full((n, 3), 5.0), 0.0
        )
        combine = lambda flow, src: jnp.where(src > 0, src, flow)
        cell = lambda w, x: (w, jnp.tanh(x * w))  # stateless: directly checkable
        weights = jnp.linspace(0.5, 1.5, 4)
        res = (
            Stream.feedback(init, n, self._emit)
            .zip(Stream.source(overlay), combine)
            .through(cell, weights, mutable_state=False)
            .collect(LazyEvaluator())
        )

        def chain_one(x):
            out, _ = lax.scan(lambda fl, w: (jnp.tanh(fl * w), w), x, weights)
            return self._emit(out)

        # gated items (0, 3, 6) — including the *fed-back* items 3 and 6
        # — must equal running the chain on the overlay value alone.
        expect = chain_one(jnp.full((3,), 5.0))
        for b in (0, 3, 6):
            np.testing.assert_allclose(
                np.asarray(res.items[b]), np.asarray(expect), rtol=1e-6
            )
        # a non-gated fed-back item really is emit(chain(prev emitted))
        np.testing.assert_allclose(
            np.asarray(res.items[4]),
            np.asarray(chain_one(res.items[2])),
            rtol=1e-6,
        )

    def test_num_items_and_lag_validation(self):
        with pytest.raises(ValueError, match="num_items"):
            Stream.feedback(jnp.zeros((4, 2)), 3, self._emit)

    def test_lazy_eval_graph_rejects_feedback(self):
        s = Stream.feedback(jnp.zeros((2, 3)), 6, self._emit).through(
            _count_cell, jnp.zeros(2, jnp.int32)
        )
        with pytest.raises(TypeError, match="node-local"):
            G.lazy_eval_graph(s.node)

    def test_emit_must_preserve_structure(self):
        s = Stream.feedback(
            jnp.zeros((2, 3)), 6, lambda item: {"changed": item}
        ).through(_count_cell, jnp.zeros(2, jnp.int32))
        with pytest.raises(ValueError, match="preserve the flowing item"):
            s.collect(LazyEvaluator())

    def test_tail_zip_rejected(self):
        src = Stream.source(jnp.zeros((6, 3)))
        s = (
            Stream.feedback(jnp.zeros((2, 3)), 6, self._emit)
            .through(_count_cell, jnp.zeros(2, jnp.int32))
            .zip(src, lambda a, b: a + b)
        )
        with pytest.raises(ValueError, match="after the last cell"):
            s.lower()

    def test_tail_map_folds_into_emit(self):
        """Maps after the last segment run before the emit — the
        collected items are the emitted (post-tail-map) values."""
        init = jnp.ones((2, 3))
        base = Stream.feedback(init, 6, self._emit).through(
            _count_cell, jnp.zeros(2, jnp.int32)
        )
        mapped = (
            Stream.feedback(init, 6, lambda it: self._emit(it * 2.0))
            .through(_count_cell, jnp.zeros(2, jnp.int32))
        )
        with_tail = (
            Stream.feedback(init, 6, self._emit)
            .through(_count_cell, jnp.zeros(2, jnp.int32))
            .map(lambda x: x * 2.0)
        )
        a = with_tail.collect(LazyEvaluator()).items
        b = mapped.collect(LazyEvaluator()).items
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert with_tail.lower().finalize is None

    def test_plan_has_feedback_lag(self):
        from repro.core.schedules import build_plan

        p = build_plan("gpipe", 4, 16, feedback_lag=8)
        assert p.feedback_lag == 8
        # every (position, item) unit scheduled exactly once
        assert int((p.microbatch >= 0).sum()) == 4 * 16


class TestPerCellState:
    """``run_chain_sequential`` (Lazy feedback chains) keeps each cell's
    mutable state in its own loop buffer and applies the cells by a
    static loop: outputs and final states equal a hand-rolled loop over
    items and cells, for one cell, a few and many, and for a chain whose
    interior injection splits it into two segments (the unified
    multi-segment state).  Integer arithmetic, so equal means bitwise."""

    @staticmethod
    def _cell(const, state, x):
        acc = (state["acc"] * 5 + x * const + state["n"]) % 1009
        return {"acc": acc, "n": state["n"] + 1}, (x * 3 + acc) % 997

    @staticmethod
    def _emit(x):
        return (x * 7 + 1) % 991

    @staticmethod
    def _combine(flow, src):
        return (flow + src) % 983

    @staticmethod
    def _ints(shape, seed):
        return jnp.asarray(
            np.random.default_rng(seed).integers(0, 1000, size=shape),
            jnp.int32,
        )

    def _segment(self, num_cells, seed):
        state = {
            "acc": self._ints((num_cells, 3), seed),
            "n": jnp.arange(num_cells, dtype=jnp.int32) * (seed + 1),
        }
        return state, self._ints((num_cells, 1), seed + 100)

    def _reference(self, init, n, segments, src):
        lag = init.shape[0]
        cells = [
            (jax.tree.map(lambda l, _c=c: l[_c], state), const[c], k)
            for k, (state, const) in enumerate(segments)
            for c in range(const.shape[0])
        ]
        outs = []
        for b in range(n):
            flow = init[b] if b < lag else outs[b - lag]
            for c, (state, const, k) in enumerate(cells):
                if c > 0 and cells[c - 1][2] != k:
                    flow = self._combine(flow, src[b])
                new, flow = self._cell(const, state, flow)
                cells[c] = (new, const, k)
            outs.append(self._emit(flow))
        finals = tuple(
            jax.tree.map(
                lambda *ls: jnp.stack(ls),
                *[st for st, _, k in cells if k == seg],
            )
            for seg in range(len(segments))
        )
        return jnp.stack(outs), finals

    @pytest.mark.parametrize(
        "cells", [(1,), (3,), (8,), (3, 2)],
        ids=["1_cell", "3_cells", "8_cells", "interior_zip_3_2"],
    )
    def test_matches_hand_rolled_loop(self, cells):
        lag, n = 3, 11
        init = self._ints((lag, 3), 5)
        src = self._ints((n, 3), 6)
        segments = [self._segment(c, seed=k) for k, c in enumerate(cells)]
        stream = Stream.feedback(init, n, self._emit)
        for k, (state, const) in enumerate(segments):
            if k > 0:
                stream = stream.zip(Stream.source(src), self._combine)
            stream = stream.through(self._cell, state, const_state=const)
        chain = stream.lower()
        assert chain.num_cells == sum(cells)
        assert [i.cell_index for i in chain.injections[1:]] == [
            sum(cells[:k]) for k in range(1, len(cells))
        ]
        res = stream.collect(LazyEvaluator())
        ref_items, ref_states = self._reference(init, n, segments, src)
        np.testing.assert_array_equal(np.asarray(res.items), np.asarray(ref_items))
        assert len(res.states) == len(cells)
        for got, want in zip(res.states, ref_states):
            for key in ("acc", "n"):
                np.testing.assert_array_equal(
                    np.asarray(got[key]), np.asarray(want[key])
                )


class TestLowering:
    def test_entry_zip_two_injections(self):
        x, y = _items(seed=1), _items(seed=2)
        s = (
            Stream.source(x)
            .zip(Stream.source(y), lambda a, b: a + b)
            .through(_count_cell, jnp.arange(4, dtype=jnp.int32))
        )
        chain = s.lower()
        assert len(chain.injections) == 2
        assert [i.cell_index for i in chain.injections] == [0, 0]
        assert chain.injections[0].combine is None
        assert chain.injections[1].combine is not None

    def test_interior_zip_cell_index(self):
        x, y = _items(seed=1), _items(seed=2)
        s = (
            Stream.source(x)
            .through(_count_cell, jnp.arange(4, dtype=jnp.int32))
            .zip(Stream.source(y), lambda a, b: a + b)
            .through(_count_cell, jnp.arange(2, dtype=jnp.int32))
        )
        chain = s.lower()
        assert chain.num_cells == 6
        assert [i.cell_index for i in chain.injections] == [0, 4]

    def test_pure_program_zero_cells(self):
        s = Stream.source(_items()).map(lambda x: x * 3.0)
        chain = s.lower()
        assert chain.num_cells == 0 and len(chain.segments) == 0

    def test_lazy_future_zero_cell_paths_agree(self):
        from repro.core.stream import FutureEvaluator  # noqa: F401
        s = Stream.source(_items()).map(lambda x: x * 3.0)
        # Zero-cell chains never enter the pipeline region, so the Future
        # evaluator's chain path is pure data plumbing — exercised here
        # without a mesh via the lowered chain itself.
        chain = s.lower()
        outs = chain.injections[0].materialize()
        np.testing.assert_array_equal(
            np.asarray(outs), np.asarray(s.collect().items)
        )


class TestConstState:
    """The read-only/mutable state split: ``through(..., const_state=...)``.

    Const leaves ride scan xs only — same values as folding them into
    the mutable state, minus the per-tick write-back (and minus an entry
    in the returned final states).  Lazy-side laws here; the Lazy ≡
    Future bit-equality across the schedule zoo (including feedback
    chains) runs in the multidevice battery.
    """

    @staticmethod
    def _const_cell(const, state, item):
        return state + 1, jnp.tanh(item * const) + state * 0.01

    @staticmethod
    def _folded_cell(state, item):
        new = {"count": state["count"] + 1, "scale": state["scale"]}
        return new, jnp.tanh(item * state["scale"]) + state["count"] * 0.01

    def _w(self, n=4):
        return jnp.arange(n, dtype=jnp.float32)

    def _scale(self, n=4):
        return jnp.linspace(1.0, 2.0, n)

    def test_const_equals_folded_state(self):
        items = _items()
        a = (
            Stream.source(items)
            .through(self._const_cell, self._w(), const_state=self._scale())
            .collect()
        )
        b = (
            Stream.source(items)
            .through(
                self._folded_cell,
                {"count": self._w(), "scale": self._scale()},
            )
            .collect()
        )
        np.testing.assert_array_equal(np.asarray(a.items), np.asarray(b.items))
        # final states cover the mutable half only
        np.testing.assert_array_equal(
            np.asarray(a.states[0]), np.asarray(b.states[0]["count"])
        )

    def test_const_leading_axis_validated(self):
        with pytest.raises(ValueError, match="const_state"):
            Stream.source(_items()).through(
                self._const_cell, self._w(4), const_state=self._scale(3)
            )

    def test_const_under_feedback(self):
        emit = lambda x: x * 0.9 + 0.1
        init = _items(3)
        a = (
            Stream.feedback(init, 11, emit)
            .through(self._const_cell, self._w(), const_state=self._scale())
            .collect()
        )
        b = (
            Stream.feedback(init, 11, emit)
            .through(
                self._folded_cell,
                {"count": self._w(), "scale": self._scale()},
            )
            .collect()
        )
        np.testing.assert_array_equal(np.asarray(a.items), np.asarray(b.items))

    def test_const_multi_segment_with_mid_map(self):
        """Unified multi-segment machinery: a const segment composed with
        a const-free one through a fused mid-spine map (the pre_fn path),
        against the same program with const folded into mutable state."""
        items = _items()
        plain = lambda s, x: (s, jnp.tanh(x * s))
        w2 = jnp.linspace(0.5, 1.5, 3)
        a = (
            Stream.source(items)
            .through(self._const_cell, self._w(), const_state=self._scale())
            .map(lambda x: x * 0.5)
            .through(plain, w2, mutable_state=False)
            .collect()
        )
        b = (
            Stream.source(items)
            .through(
                self._folded_cell,
                {"count": self._w(), "scale": self._scale()},
            )
            .map(lambda x: x * 0.5)
            .through(plain, w2, mutable_state=False)
            .collect()
        )
        np.testing.assert_array_equal(np.asarray(a.items), np.asarray(b.items))
        assert len(a.states) == 2

    def test_const_never_returned_or_mutated(self):
        """A cell trying to 'write' const has nowhere to put it: the
        returned state structure is the mutable half, and collect's
        states match it."""
        items = _items()
        res = (
            Stream.source(items)
            .through(self._const_cell, self._w(), const_state=self._scale())
            .collect()
        )
        assert len(res.states) == 1
        assert np.asarray(res.states[0]).shape == (4,)


class TestBenchCheckGate:
    """Satellite: the --check regression gate's pure diff logic."""

    def _rec(self, schedule="gpipe", m=4, seconds=1.0):
        return {
            "schedule": schedule,
            "devices": 4,
            "interleave": 1,
            "virtual_stages": 4,
            "num_microbatches": m,
            "dim": 256,
            "rows": 4096,
            "measured_seconds": seconds,
            "modeled_bubble": 0.1,
            "modeled_ticks": 10,
        }

    def test_no_regression_within_tolerance(self):
        from benchmarks.run import check_regressions

        base = [self._rec(seconds=1.0)]
        fresh = [self._rec(seconds=1.05)]
        assert check_regressions(base, fresh, 0.10) == []

    def test_regression_detected(self):
        from benchmarks.run import check_regressions

        base = [self._rec(seconds=1.0), self._rec(m=8, seconds=2.0)]
        fresh = [self._rec(seconds=1.25), self._rec(m=8, seconds=2.05)]
        out = check_regressions(base, fresh, 0.10)
        assert len(out) == 1
        assert out[0]["num_microbatches"] == 4
        assert out[0]["ratio"] == pytest.approx(1.25)

    def test_size_mismatch_not_compared(self):
        from benchmarks.run import check_regressions

        base = [self._rec(seconds=1.0)]
        fresh = [dict(self._rec(seconds=9.0), dim=512)]
        assert check_regressions(base, fresh, 0.10) == []

    def test_missing_baseline_message_not_keyerror(self, tmp_path, capsys):
        from benchmarks.run import _load_baseline

        assert _load_baseline("serve", str(tmp_path / "nope.json")) is None
        err = capsys.readouterr().err
        assert "--suite serve" in err and "no baseline" in err

    def test_baseline_without_sweep_key_is_explained(self, tmp_path, capsys):
        import json as _json

        from benchmarks.run import _load_baseline

        p = tmp_path / "BENCH_serve.json"
        p.write_text(_json.dumps({"rows": []}))
        assert _load_baseline("serve", str(p)) is None
        assert "'sweep'" in capsys.readouterr().err

    def test_corrupt_baseline_is_explained(self, tmp_path, capsys):
        from benchmarks.run import _load_baseline

        p = tmp_path / "BENCH_serve.json"
        p.write_text("not json")
        assert _load_baseline("serve", str(p)) is None
        assert "unreadable" in capsys.readouterr().err

    def test_check_rejects_unknown_suite(self, capsys):
        from benchmarks.run import run_check

        assert run_check(0.1, False, only="nosuch") == 2
        assert "no gate for suite" in capsys.readouterr().err
