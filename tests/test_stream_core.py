"""Core Stream/Future construct: semantics, chunking math, combinators."""
import pytest

from _hypothesis_stub import hypothesis, st  # skips @given tests offline
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Future,
    FutureEvaluator,
    LazyEvaluator,
    Stream,
    StreamProgram,
    bubble_fraction,
    build_backward_plan,
    build_combined_plan,
    build_plan,
    chunk_axis,
    defer,
    evaluate,
    feed_peak_items,
    optimal_num_chunks,
    optimal_schedule,
    pipeline_step_time,
    schedule_bubble_fraction,
    schedule_peak_items,
    schedule_ticks,
    unchunk_axis,
)
from repro.core.future import HostFuture
from repro.core.schedules import UNIT_B, UNIT_F, UNIT_W


def _counting_program(num_cells):
    def cell(state, item):
        return state + 1, item * 1.5 + state.astype(jnp.float32)

    return StreamProgram(cell, jnp.arange(num_cells, dtype=jnp.int32), num_cells)


class TestLazyEvaluator:
    def test_matches_python_reference(self):
        prog = _counting_program(3)
        items = jnp.asarray([[1.0], [2.0]])
        states, outs = evaluate(prog, items, LazyEvaluator())
        # python reference with the same ordering semantics
        st_ref = np.arange(3, dtype=np.int64)
        outs_ref = []
        for it in [1.0, 2.0]:
            flow = it
            for s in range(3):
                flow = flow * 1.5 + st_ref[s]
                st_ref[s] += 1
            outs_ref.append(flow)
        np.testing.assert_array_equal(np.asarray(states), st_ref)
        np.testing.assert_allclose(np.asarray(outs)[:, 0], outs_ref, rtol=1e-6)

    def test_state_mutation_order(self):
        # each cell counts items seen: all cells see all items
        prog = _counting_program(4)
        items = jnp.ones((5, 1))
        states, _ = evaluate(prog, items)
        np.testing.assert_array_equal(
            np.asarray(states), np.arange(4) + 5
        )

    def test_immutable_state(self):
        def cell(w, x):
            return w + 1, x * w

        prog = StreamProgram(cell, jnp.ones(2), 2, mutable_state=False)
        states, outs = evaluate(prog, jnp.ones((3, 1)))
        np.testing.assert_array_equal(np.asarray(states), np.ones(2))

    def test_bad_state_shape_raises(self):
        with pytest.raises(ValueError):
            StreamProgram(lambda s, x: (s, x), jnp.zeros((3,)), 4)


class TestChunking:
    @hypothesis.given(
        st.integers(min_value=1, max_value=64),
        st.integers(min_value=1, max_value=64),
    )
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_bubble_fraction_bounds(self, s, m):
        frac = bubble_fraction(s, m)
        assert 0.0 <= frac < 1.0
        if s == 1:
            assert frac == 0.0

    @hypothesis.given(
        st.floats(min_value=1e-3, max_value=10.0),
        st.integers(min_value=2, max_value=32),
        st.floats(min_value=1e-6, max_value=1e-1),
    )
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_optimal_chunks_is_argmin(self, work, stages, overhead):
        m_star = optimal_num_chunks(work, stages, overhead)
        t_star = pipeline_step_time(work, stages, m_star, overhead)
        for m in {max(1, m_star // 2), m_star * 2, 1, 4096}:
            assert t_star <= pipeline_step_time(work, stages, m, overhead) * 1.0001

    def test_paper_primes_regime(self):
        # fine-grained cells (overhead >> work/cell): don't pipeline
        assert optimal_num_chunks(1e-4, 8, 1e-2) == 1

    def test_chunk_roundtrip(self):
        tree = {"a": jnp.arange(24).reshape(12, 2), "b": jnp.arange(12)}
        again = unchunk_axis(chunk_axis(tree, 4))
        for k in tree:
            np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(again[k]))

    def test_chunk_indivisible_raises(self):
        with pytest.raises(ValueError):
            chunk_axis(jnp.arange(10), 3)


class TestCopyBytesTerm:
    """The per-tick state-copy term (the serving cache-traffic model)."""

    def test_step_time_additive_per_tick(self):
        base = pipeline_step_time(1.0, 4, 8, 1e-3)
        with_copy = pipeline_step_time(1.0, 4, 8, 1e-3, per_tick_copy=2e-3)
        ticks = schedule_ticks("gpipe", 4, 8, handoff=1)
        assert with_copy == pytest.approx(base + ticks * 2e-3)

    def test_copy_pushes_chunks_down(self):
        # a fixed per-tick copy behaves like overhead in the M* closed
        # form: heavy write-back => fewer, bigger chunks
        light = optimal_num_chunks(1.0, 4, 1e-4)
        heavy = optimal_num_chunks(1.0, 4, 1e-4, per_tick_copy=1e-2)
        assert heavy < light

    def test_copy_term_reaches_joint_pick(self):
        # interleaving multiplies tick count; a big per-tick copy must be
        # able to flip the winner away from the high-V schedule
        free = optimal_schedule(1.0, 4, 1e-4, interleave_options=(1, 4))
        taxed = optimal_schedule(
            1.0, 4, 1e-4, interleave_options=(1, 4), per_tick_copy=5e-2
        )
        assert free.modeled_time < taxed.modeled_time
        assert taxed.num_chunks <= free.num_chunks

    def test_copy_time_conversion_validates(self):
        from repro.core.chunking import copy_time_per_tick

        assert copy_time_per_tick(1e9, 50e9) == pytest.approx(0.02)
        with pytest.raises(ValueError, match="copy_bytes_per_second"):
            copy_time_per_tick(1.0, 0.0)

    def test_decode_row_bytes_are_maxlen_smaller_than_slab(self):
        from repro.configs.registry import get_config, smoke_config
        from repro.serve.engine import decode_copy_bytes_per_tick

        cfg = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=8)
        rows = decode_copy_bytes_per_tick(cfg, 4, 8)
        slab = decode_copy_bytes_per_tick(
            cfg, 4, 8, row_scatter=False, max_len=256
        )
        assert rows > 0
        # attention K/V dominates this config: the slab term is the row
        # term scaled by max_len
        assert slab == rows * 256

    def test_suggest_decode_pipeline_threads_the_term(self):
        from repro.configs.registry import get_config, smoke_config
        from repro.serve.engine import suggest_decode_pipeline

        cfg = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=8)
        row_pick = suggest_decode_pipeline(
            cfg, devices=4, work_per_item=1e-3, per_tick_overhead=1e-7,
            microbatch=4, num_cells=8, copy_bytes_per_second=1e9,
        )
        slab_pick = suggest_decode_pipeline(
            cfg, devices=4, work_per_item=1e-3, per_tick_overhead=1e-7,
            microbatch=4, num_cells=8, copy_bytes_per_second=1e9,
            row_scatter=False,
        )
        # the slab scheme's max_len-times-larger traffic shows up as a
        # slower modeled step and (generally) fewer chunks
        assert slab_pick.modeled_time > row_pick.modeled_time


class TestSchedulePlans:
    """The analytic chunking model must match the tick tables the
    schedules actually emit — modeled bubble == measured bubble."""

    GRID = [
        (name, d, m, v)
        for name in ("gpipe", "one_f_one_b")
        for d in (1, 2, 3, 4, 8)
        for m in (1, 2, 4, 5, 8, 16)
        for v in (1,)
    ] + [
        ("interleaved", d, m, v)
        for d in (2, 3, 4)
        for m in (1, 2, 4, 5, 8, 16)
        for v in (2, 3, 4)
    ]

    def test_model_ticks_match_plans(self):
        for name, d, m, v in self.GRID:
            plan = build_plan(name, d, m, v)
            assert plan.num_ticks == schedule_ticks(
                name, d, m, v, handoff=plan.handoff
            ), (name, d, m, v)

    def test_model_bubble_matches_plans(self):
        for name, d, m, v in self.GRID:
            plan = build_plan(name, d, m, v)
            modeled = schedule_bubble_fraction(name, d, m, v, handoff=plan.handoff)
            assert abs(plan.bubble_fraction - modeled) < 1e-9, (name, d, m, v)

    def test_interleaving_shrinks_bubble(self):
        g = build_plan("gpipe", 4, 8)
        i2 = build_plan("interleaved", 4, 8, 2)
        i4 = build_plan("interleaved", 4, 8, 4)
        assert i4.bubble_fraction < i2.bubble_fraction < g.bubble_fraction

    def test_every_unit_scheduled_once(self):
        for name, d, m, v in [("gpipe", 4, 8, 1), ("interleaved", 4, 8, 2)]:
            plan = build_plan(name, d, m, v)
            seen = set()
            for t in range(plan.num_ticks):
                for dev in range(d):
                    mb = plan.microbatch[t, dev]
                    if mb >= 0:
                        unit = (int(plan.group[t, dev]) * d + dev, int(mb))
                        assert unit not in seen
                        seen.add(unit)
            assert len(seen) == d * v * m

    def test_collection_only_on_last_stage(self):
        for name, d, m, v in [("gpipe", 4, 8, 1), ("interleaved", 4, 8, 2)]:
            plan = build_plan(name, d, m, v)
            assert plan.collect[:, : d - 1].sum() == 0
            assert plan.collect[:, d - 1].sum() == m

    def test_emit_column_zero_without_feedback(self):
        for name, d, m, v in [("gpipe", 4, 8, 1), ("interleaved", 4, 8, 2)]:
            plan = build_plan(name, d, m, v)
            assert plan.emit.sum() == 0

    def test_emit_column_is_last_stage_only_under_feedback(self):
        """The plan-level half of the emit split: emit placement equals
        collect (every final-position unit emits, once per item) and is
        confined to the final-stage device — the contract the evaluator's
        sole head region keys off."""
        for name, d, m, v, lag in [
            ("gpipe", 4, 16, 1, 8),
            ("gpipe", 4, 16, 1, 4),
            ("one_f_one_b", 4, 16, 1, 8),
            ("interleaved", 4, 16, 2, 8),
            ("gpipe", 2, 8, 1, 2),
        ]:
            plan = build_plan(name, d, m, v, feedback_lag=lag)
            assert (plan.emit == plan.collect).all(), (name, d, m, v, lag)
            assert plan.emit[:, : d - 1].sum() == 0, (name, d, m, v, lag)
            assert plan.emit[:, d - 1].sum() == m, (name, d, m, v, lag)

    def test_peak_items_ordering(self):
        # 1F1B's whole point: stash min(S, M) microbatches, not M
        assert schedule_peak_items("one_f_one_b", 4, 16) == 4
        assert schedule_peak_items("gpipe", 4, 16) == 16

    def test_optimal_schedule_joint_pick(self):
        # bubble-dominated regime: interleaving wins
        choice = optimal_schedule(1.0, 8, 1e-6, max_chunks=64)
        assert choice.schedule == "interleaved"
        # overhead-dominated: plain schedules, tiny M (paper's primes case)
        choice = optimal_schedule(1e-4, 8, 1e-2, max_chunks=64)
        assert choice.interleave == 1 and choice.num_chunks == 1
        # memory budget forces off gpipe (gpipe peak is always 1.0
        # items) — a planned-backward job, where schedules' stash
        # bounds are real and a sub-1.0 budget is satisfiable at all
        choice = optimal_schedule(
            1.0, 8, 1e-4, max_chunks=256, memory_budget_items=0.5,
            backward="planned",
        )
        assert choice.schedule != "gpipe"
        assert (
            schedule_peak_items(
                choice.schedule, 8, choice.num_chunks, choice.interleave
            )
            / choice.num_chunks
            <= 0.5
        )

    def test_schedule_validation(self):
        with pytest.raises(ValueError):
            build_plan("zigzag", 4, 8)
        with pytest.raises(ValueError):
            build_plan("gpipe", 4, 8, interleave=2)


class TestMultiInjectionPlans:
    """The generalized feed carousel: per-source columns for multi-source
    streams injecting at arbitrary virtual-stage boundaries."""

    GRID = [
        ("gpipe", 4, 8, 1, (0, 2)),
        ("gpipe", 4, 5, 1, (0, 0, 3)),
        ("one_f_one_b", 4, 8, 1, (0, 1)),
        ("interleaved", 4, 8, 2, (0, 5)),
        ("interleaved", 2, 6, 3, (0, 4)),
    ]

    def test_injections_never_change_the_makespan(self):
        for name, d, m, v, pos in self.GRID:
            plain = build_plan(name, d, m, v)
            multi = build_plan(name, d, m, v, inject_positions=pos)
            assert multi.num_ticks == plain.num_ticks, (name, d, m, v, pos)
            np.testing.assert_array_equal(multi.microbatch, plain.microbatch)

    def test_each_source_consumed_exactly_m_times(self):
        for name, d, m, v, pos in self.GRID:
            plan = build_plan(name, d, m, v, inject_positions=pos)
            assert plan.num_sources == len(pos)
            np.testing.assert_array_equal(
                plan.src_consume.sum(axis=1), [m] * len(pos)
            )

    def test_reload_every_dth_consumption(self):
        for name, d, m, v, pos in self.GRID:
            plan = build_plan(name, d, m, v, inject_positions=pos)
            for s in range(len(pos)):
                # reloads happen on consumptions 0, D, 2D, ...
                assert plan.src_feed_reload[s].sum() == -(-m // d)
                ticks = np.nonzero(plan.src_feed_reload[s])[0]
                np.testing.assert_array_equal(
                    plan.src_feed_idx[s][ticks], np.arange(len(ticks))
                )

    def test_inject_devices_follow_positions(self):
        plan = build_plan("interleaved", 4, 8, 2, inject_positions=(0, 5))
        assert plan.inject_devices == (0, 1)  # virtual stage 5 on device 1

    def test_legacy_columns_alias_source_zero(self):
        plan = build_plan("gpipe", 4, 8, inject_positions=(0, 2))
        np.testing.assert_array_equal(plan.feed_reload, plan.src_feed_reload[0])
        np.testing.assert_array_equal(plan.feed_idx, plan.src_feed_idx[0])
        np.testing.assert_array_equal(plan.feed_advance, plan.src_feed_advance[0])
        np.testing.assert_array_equal(plan.inject, plan.src_consume[0])

    def test_position_validation(self):
        with pytest.raises(ValueError, match="chain entry"):
            build_plan("gpipe", 4, 8, inject_positions=(1,))
        with pytest.raises(ValueError, match="outside"):
            build_plan("gpipe", 4, 8, inject_positions=(0, 4))
        with pytest.raises(ValueError, match="outside"):
            build_plan("interleaved", 4, 8, 2, inject_positions=(0, 8))

    def test_plan_peak_charges_its_own_sources(self):
        # the plan's self-reported peak must use the same multi-source
        # model optimal_schedule budgets against
        single = build_plan("gpipe", 4, 8)
        multi = build_plan("gpipe", 4, 8, inject_positions=(0, 2))
        assert single.peak_inflight_items == 8
        assert multi.peak_inflight_items == schedule_peak_items(
            "gpipe", 4, 8, num_sources=2
        )
        assert multi.peak_inflight_items > single.peak_inflight_items

    def test_feed_memory_terms(self):
        # one source: shard + register; each extra source adds the same
        assert feed_peak_items(4, 8, 1) == 3
        assert feed_peak_items(4, 8, 2) == 6
        assert feed_peak_items(4, 5, 2) == 2 * (2 + 1)
        with pytest.raises(ValueError):
            feed_peak_items(4, 8, 0)
        # schedule peak charges extra sources' feeds, primary grandfathered
        base = schedule_peak_items("one_f_one_b", 4, 16)
        multi = schedule_peak_items("one_f_one_b", 4, 16, num_sources=3)
        assert multi == base + 2 * (4 + 1)

    def test_multi_source_budget_shifts_choice(self):
        # same regime, but feed storage charged against the budget: more
        # sources must never *relax* the constraint
        one = optimal_schedule(
            1.0, 8, 1e-4, max_chunks=256, memory_budget_items=0.6,
            backward="planned",
        )
        many = optimal_schedule(
            1.0, 8, 1e-4, max_chunks=256, memory_budget_items=0.6,
            num_sources=4, backward="planned",
        )
        assert many.peak_items >= one.peak_items
        assert (
            schedule_peak_items(
                many.schedule, 8, many.num_chunks, many.interleave, 4
            )
            / many.num_chunks
            <= 0.6
        )


class TestCombinedPlans:
    """Combined fwd+bwd tick plans: the backward as first-class units,
    with the 1F1B stash bound asserted from the plan columns."""

    GRID = [
        (name, d, m, v)
        for name in ("gpipe", "one_f_one_b")
        for d in (1, 2, 4, 8)
        for m in (1, 2, 4, 5, 8, 16)
        for v in (1,)
    ] + [
        ("interleaved", d, m, v)
        for d in (2, 3, 4)
        for m in (2, 4, 5, 8)
        for v in (2, 3)
    ]

    def test_one_f_one_b_peak_stash_is_min_s_m(self):
        # THE acceptance assert: peak concurrently-stashed activations,
        # computed from the stash/release columns, is min(S, M) for the
        # 1F1B combined plan vs M for gpipe's fill-then-drain.
        for d in (2, 4, 8):
            for m in (1, 2, 4, 5, 8, 16):
                cp = build_combined_plan("one_f_one_b", d, m)
                assert cp.peak_stash_items == min(d, m), (d, m)
                cg = build_combined_plan("gpipe", d, m)
                assert cg.peak_stash_items == m, (d, m)

    def test_peak_matches_planned_closed_form(self):
        # the chunking model's backward="planned" term is exact against
        # the combined plans' own columns — measured, not assumed
        for name, d, m, v in self.GRID:
            cp = build_combined_plan(name, d, m, v)
            assert cp.peak_stash_items == schedule_peak_items(
                name, d, m, v, backward="planned"
            ), (name, d, m, v)
            assert cp.num_stash_slots == cp.peak_stash_items

    def test_autodiff_peak_is_every_unit_input(self):
        # autodiff's fwd/bwd phase boundary keeps all V*M inputs live
        # regardless of schedule name
        assert schedule_peak_items("one_f_one_b", 4, 16, backward="autodiff") == 16
        assert schedule_peak_items("gpipe", 4, 16, backward="autodiff") == 16
        assert (
            schedule_peak_items("interleaved", 4, 8, 2, backward="autodiff")
            == 16
        )
        with pytest.raises(ValueError, match="backward"):
            schedule_peak_items("gpipe", 4, 8, backward="zigzag")

    def test_every_unit_scheduled_once_and_deps_hold(self):
        for name, d, m, v, split in [
            ("gpipe", 4, 8, 1, False),
            ("one_f_one_b", 4, 8, 1, False),
            ("one_f_one_b", 4, 5, 1, True),
            ("interleaved", 2, 6, 2, False),
        ]:
            cp = build_combined_plan(name, d, m, v, split_backward=split)
            p_ = d * v
            tick_of = {}
            for t in range(cp.num_ticks):
                for dev in range(d):
                    if cp.kind[t, dev] < 0:
                        continue
                    unit = (
                        int(cp.kind[t, dev]),
                        int(cp.position[t, dev]),
                        int(cp.microbatch[t, dev]),
                    )
                    assert unit not in tick_of, unit
                    assert cp.position[t, dev] % d == dev
                    tick_of[unit] = t
            kinds = (UNIT_F, UNIT_B, UNIT_W) if split else (UNIT_F, UNIT_B)
            assert len(tick_of) == p_ * m * len(kinds)
            h = cp.handoff
            for mm in range(m):
                for p in range(p_):
                    if p > 0:
                        assert (
                            tick_of[(UNIT_F, p, mm)]
                            >= tick_of[(UNIT_F, p - 1, mm)] + h
                        )
                    if p < p_ - 1:
                        assert (
                            tick_of[(UNIT_B, p, mm)]
                            >= tick_of[(UNIT_B, p + 1, mm)] + h
                        )
                    if split:
                        assert (
                            tick_of[(UNIT_W, p, mm)] > tick_of[(UNIT_B, p, mm)]
                        )
                # loss turnaround: B at the last position strictly after F
                assert (
                    tick_of[(UNIT_B, p_ - 1, mm)] > tick_of[(UNIT_F, p_ - 1, mm)]
                )

    def test_gpipe_is_phase_gated(self):
        cp = build_combined_plan("gpipe", 4, 8)
        last_f = max(
            t
            for t in range(cp.num_ticks)
            for dev in range(4)
            if cp.kind[t, dev] == UNIT_F
        )
        first_b = min(
            t
            for t in range(cp.num_ticks)
            for dev in range(4)
            if cp.kind[t, dev] == UNIT_B
        )
        assert first_b > last_f

    def test_one_f_one_b_interleaves(self):
        # not phase-gated: some B unit runs before the last F unit
        cp = build_combined_plan("one_f_one_b", 4, 8)
        last_f = max(
            t
            for t in range(cp.num_ticks)
            for dev in range(4)
            if cp.kind[t, dev] == UNIT_F
        )
        first_b = min(
            t
            for t in range(cp.num_ticks)
            for dev in range(4)
            if cp.kind[t, dev] == UNIT_B
        )
        assert first_b < last_f

    def test_stash_release_columns_pair_up(self):
        for name in ("gpipe", "one_f_one_b"):
            cp = build_combined_plan(name, 4, 6)
            for dev in range(4):
                stashes = int((cp.stash_slot[:, dev] >= 0).sum())
                releases = int((cp.release_slot[:, dev] >= 0).sum())
                assert stashes == releases  # every stash freed exactly once
                assert (cp.stash_slot[:, dev].max() if stashes else -1) < (
                    cp.num_stash_slots
                )

    def test_split_backward_groundwork(self):
        # ZB 3-way split: W units exist, release moves to W, and the
        # stash bound is unchanged (B still consumes before W frees)
        cp = build_combined_plan("one_f_one_b", 4, 6, split_backward=True)
        assert set(np.unique(cp.kind)) >= {UNIT_F, UNIT_B, UNIT_W}
        assert cp.split_backward
        # releases happen at W ticks only
        for t in range(cp.num_ticks):
            for dev in range(4):
                if cp.release_slot[t, dev] >= 0:
                    assert cp.kind[t, dev] == UNIT_W

    def test_backward_plan_is_the_mirror(self):
        for name, d, m, v in [
            ("gpipe", 4, 8, 1),
            ("one_f_one_b", 4, 5, 1),
            ("interleaved", 2, 6, 2),
        ]:
            bp = build_backward_plan(name, d, m, v)
            fp = build_plan(name, d, m, v)
            assert bp.num_ticks == fp.num_ticks
            # cotangent seeds feed device D-1; d_items emit on device 0
            assert bp.inject_devices == (d - 1,)
            assert bp.collect[:, 0].sum() == m
            assert bp.collect[:, 1:].sum() == 0
            # every B unit once, per-position microbatch order ascending
            per_pos: dict = {}
            for t in range(bp.num_ticks):
                for dev in range(d):
                    mb = bp.microbatch[t, dev]
                    if mb >= 0:
                        pos = int(bp.group[t, dev]) * d + dev
                        per_pos.setdefault(pos, []).append(int(mb))
            assert sorted(per_pos) == list(range(d * v))
            for pos, seq in per_pos.items():
                assert seq == sorted(seq) == list(range(m)), (name, pos)

    def test_combined_plan_b_order_matches_backward_plan(self):
        # the custom-VJP bwd phase (backward plan) replays the combined
        # plan's B units: per device, identical (position, m) sequences
        for name, d, m, v in [("one_f_one_b", 4, 6, 1), ("gpipe", 4, 6, 1)]:
            cp = build_combined_plan(name, d, m, v)
            bp = build_backward_plan(name, d, m, v)
            for dev in range(d):
                comb = [
                    (int(cp.position[t, dev]), int(cp.microbatch[t, dev]))
                    for t in range(cp.num_ticks)
                    if cp.kind[t, dev] == UNIT_B
                ]
                mirror = [
                    (int(bp.group[t, dev]) * d + dev, int(bp.microbatch[t, dev]))
                    for t in range(bp.num_ticks)
                    if bp.microbatch[t, dev] >= 0
                ]
                assert comb == mirror, (name, dev)

    def test_optimal_schedule_flips_to_one_f_one_b_under_planned(self):
        # satellite: the planned backward makes 1F1B's memory advantage
        # real — a budget only its min(S, M) stash fits now selects it
        # (V=1 search: interleaving is a separate, bubble-driven win)
        kw = dict(
            max_chunks=64, memory_budget_items=0.2, interleave_options=(1,)
        )
        choice = optimal_schedule(1.0, 4, 1e-4, backward="planned", **kw)
        assert choice.schedule == "one_f_one_b"
        assert choice.peak_items / choice.num_chunks <= 0.2
        # under autodiff every schedule stashes all M: the same budget
        # is infeasible — the old model silently pretended otherwise
        with pytest.raises(ValueError, match="fits memory_budget"):
            optimal_schedule(1.0, 4, 1e-4, backward="autodiff", **kw)


class TestPlannedBackwardValidation:
    """The planned-backward executor's contract: clear errors for the
    shapes it cannot transpose (checked before any device work)."""

    def _mesh(self):
        return jax.make_mesh(
            (1,), ("pod",), devices=jax.devices()[:1]
        )

    def test_backward_mode_validated(self):
        with pytest.raises(ValueError, match="backward"):
            FutureEvaluator(self._mesh(), "pod", backward="zigzag")

    def test_mutable_state_rejected(self):
        ev = FutureEvaluator(self._mesh(), "pod", backward="planned")
        prog = StreamProgram(lambda s, x: (s + 1, x + s), jnp.zeros(2), 2)
        with pytest.raises(ValueError, match="immutable"):
            evaluate(prog, jnp.ones((2, 1)), ev)

    def test_feedback_rejected(self):
        ev = FutureEvaluator(self._mesh(), "pod", backward="planned")
        s = Stream.feedback(jnp.ones((2, 1)), 4, lambda x: x).through(
            lambda w, x: (w, x * w), jnp.ones(2), mutable_state=False
        )
        with pytest.raises(ValueError, match="feedback"):
            s.collect(ev)

    def test_multi_source_rejected(self):
        ev = FutureEvaluator(self._mesh(), "pod", backward="planned")
        s = (
            Stream.source(jnp.ones((2, 1)))
            .zip(Stream.source(jnp.ones((2, 1))), lambda a, b: a + b)
            .through(lambda w, x: (w, x * w), jnp.ones(2), mutable_state=False)
        )
        with pytest.raises(ValueError, match="single-source"):
            s.collect(ev)

    def test_integer_items_rejected(self):
        ev = FutureEvaluator(self._mesh(), "pod", backward="planned")
        prog = StreamProgram(
            lambda w, x: (w, x * 2), jnp.ones(2), 2, mutable_state=False
        )
        with pytest.raises(ValueError, match="floating-point"):
            evaluate(prog, jnp.ones((2, 1), jnp.int32), ev)

    def test_const_state_rejected(self):
        # const leaves are excluded from differentiation by construction,
        # so a planned-backward chain must refuse them loudly.
        ev = FutureEvaluator(self._mesh(), "pod", backward="planned")
        s = Stream.source(jnp.ones((2, 1))).through(
            lambda c, w, x: (w, x * w * c),
            jnp.ones(2),
            mutable_state=False,
            const_state=jnp.ones(2),
        )
        with pytest.raises(ValueError, match="const_state"):
            s.collect(ev)

    def test_pipeline_config_carries_backward(self):
        from repro.core import PipelineConfig

        cfg = PipelineConfig(
            num_stages=4, num_microbatches=8, schedule="one_f_one_b",
            backward="planned",
        )
        assert cfg.peak_stash_items == 4
        import dataclasses

        assert (
            dataclasses.replace(cfg, backward="autodiff").peak_stash_items == 8
        )
        with pytest.raises(ValueError, match="backward"):
            PipelineConfig(num_stages=4, backward="zigzag")


class TestFutureCombinators:
    def test_defer_force_identity(self):
        fut = defer(lambda: jnp.arange(3.0))
        np.testing.assert_array_equal(np.asarray(fut.force()), [0, 1, 2])

    def test_map_forwards_laziness(self):
        fut = defer(lambda: jnp.asarray(2.0)).map(lambda v: v * 3)
        assert float(fut.force()) == 6.0

    def test_force_with_anchor_inside_jit(self):
        def f(x):
            fut = defer(jnp.sin, x)
            anchor = jnp.cos(x)  # work to overlap
            return fut.force(anchor=anchor) + anchor

        x = jnp.asarray(0.7)
        assert jnp.allclose(jax.jit(f)(x), jnp.sin(x) + jnp.cos(x))

    def test_host_future(self):
        fut = HostFuture(lambda: 41).map(lambda v: v + 1)
        assert fut.force() == 42


class TestStreamProgramJit:
    def test_evaluate_inside_jit(self):
        prog = _counting_program(4)
        items = jnp.ones((3, 2))

        @jax.jit
        def run(items):
            return evaluate(prog, items)[1]

        np.testing.assert_allclose(
            np.asarray(run(items)), np.asarray(evaluate(prog, items)[1])
        )

    def test_grad_through_lazy(self):
        def cell(w, x):
            return w, jnp.tanh(x * w)

        w = jnp.full((3,), 0.5)
        prog_fn = lambda w: StreamProgram(cell, w, 3, mutable_state=False)

        def loss(w):
            _, outs = evaluate(prog_fn(w), jnp.ones((2, 1)))
            return jnp.sum(outs)

        g = jax.grad(loss)(w)
        assert g.shape == (3,)
        assert bool(jnp.all(jnp.isfinite(g)))
