"""Elastic scaling: mesh re-derivation and state re-sharding."""
import pytest

from _hypothesis_stub import hypothesis, st  # skips @given tests offline
import jax
import jax.numpy as jnp
import numpy as np

from repro.train.elastic import (
    ElasticPlan,
    choose_elastic_plan,
    choose_mesh_shape,
    remesh_state,
)


class TestChooseMeshShape:
    @hypothesis.given(st.sampled_from([8, 16, 32, 64, 128, 256, 384, 512]))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_uses_all_devices(self, n):
        plan = choose_mesh_shape(n)
        assert plan.mesh_shape[0] * plan.mesh_shape[1] == n

    @hypothesis.given(st.sampled_from([8, 16, 32, 64, 256, 512]))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_microbatches_divide_global_batch(self, n):
        plan = choose_mesh_shape(n, global_batch=256)
        assert 256 % plan.num_microbatches == 0

    def test_model_axis_shrinks_when_indivisible(self):
        plan = choose_mesh_shape(24, preferred_model=16)
        assert plan.mesh_shape == (3, 8)

    def test_halving_devices_keeps_running(self):
        # pod loss: 512 -> 256 (cordon one pod)
        before = choose_mesh_shape(512)
        after = choose_mesh_shape(256)
        assert after.mesh_shape[1] == before.mesh_shape[1] == 16
        assert after.mesh_shape[0] == before.mesh_shape[0] // 2


class TestScheduleAwareReplanning:
    """Satellite: node loss must re-run optimal_schedule, not just re-mesh
    — schedule, M and V are all pipeline-axis-dependent."""

    # Bubble-vs-overhead regime where the optimum genuinely moves with
    # pipeline depth: deep pipelines interleave, shallow ones fill/drain.
    KW = dict(
        preferred_pipeline=8,
        global_batch=256,
        work_per_item=1.0,
        per_tick_overhead=1e-5,
    )

    def test_schedule_changes_when_pipeline_axis_shrinks(self):
        before = choose_elastic_plan(16, **self.KW)  # pipe = 8
        after = choose_elastic_plan(2, **self.KW)  # pipe = 2
        assert before.mesh_shape[-1] == 8
        assert after.mesh_shape[-1] == 2
        assert before.schedule is not None and after.schedule is not None
        assert before.schedule.schedule == "interleaved"
        assert after.schedule.schedule == "gpipe"
        assert before.schedule != after.schedule

    def test_microbatches_divide_global_batch(self):
        for n in (2, 4, 8, 16, 32):
            plan = choose_elastic_plan(n, **self.KW)
            assert 256 % plan.num_microbatches == 0

    def test_unpipelined_has_no_schedule(self):
        plan = choose_elastic_plan(8, preferred_pipeline=1)
        assert plan.schedule is None
        assert plan.mesh_shape[-1] == 1
        assert plan.axis_names == ("data", "model", "pipe")

    def test_non_power_of_two_preference_keeps_pipelining(self):
        # preferred_pipeline=6 on 8 devices must land on pipe=4 (the
        # largest power-of-two divisor <= 6), not collapse to pipe=1
        plan = choose_elastic_plan(8, **{**self.KW, "preferred_pipeline": 6})
        assert plan.mesh_shape[-1] == 4
        assert plan.schedule is not None

    def test_replan_respects_memory_budget(self):
        # a planned-backward job: the budget may exploit the combined
        # plans' real stash bounds (min(S, M) for 1F1B, V*min(S, M)
        # interleaved)
        plan = choose_elastic_plan(
            16, **{**self.KW, "memory_budget_items": 0.5,
                   "backward": "planned"}
        )
        choice = plan.schedule
        assert choice is not None
        # the choice IS the plan: M constrained to divide the global
        # batch inside the search, so the budget was checked at the M
        # that actually runs
        assert plan.num_microbatches == choice.num_chunks
        assert 256 % plan.num_microbatches == 0
        from repro.core.chunking import schedule_peak_items

        peak = schedule_peak_items(
            choice.schedule, 8, plan.num_microbatches, choice.interleave,
            backward="planned",
        )
        assert peak / plan.num_microbatches <= 0.5
        # gpipe's peak/M is always 1.0: the budget must have excluded it
        assert choice.schedule != "gpipe"

    def test_autodiff_job_budget_is_honest(self):
        # the default (autodiff-backward) job cannot buy memory with
        # 1F1B: every schedule keeps all V*M unit inputs live, so a
        # sub-1.0 budget must be reported infeasible, not silently
        # scored against a stash bound the execution never realizes
        with pytest.raises(ValueError, match="fits memory_budget"):
            choose_elastic_plan(
                16, **{**self.KW, "memory_budget_items": 0.5}
            )

    @hypothesis.given(st.sampled_from([2, 4, 8, 16, 24, 48]))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_pipeline_axis_divides_devices(self, n):
        plan = choose_elastic_plan(n, **self.KW)
        pipe = plan.mesh_shape[-1]
        assert n % pipe == 0
        assert int(np.prod(plan.mesh_shape)) == n


def test_remesh_state_roundtrip():
    """Restore-then-reshard onto a new (1-device) mesh preserves values."""
    from repro.configs.registry import get_config, smoke_config
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.parallel import sharding as SH

    sc = smoke_config(get_config("olmo-1b"))
    layout = T.model_layout(sc)
    params = init_params(jax.random.PRNGKey(0), layout)
    mesh = jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    resharded = remesh_state(params, layout, SH.TRAIN_RULES, mesh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(resharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
