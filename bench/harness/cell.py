"""Run one cell once: set up, warm up, measure the window, check, report.

``run`` returns the result object that ``bench/run.py`` prints; the
numbers compared by the check go to standard error as the last lines.
"""
from __future__ import annotations

import gc
import shutil
import sys
from functools import partial
from pathlib import Path

import numpy as np

from bench.harness import check, serve, spec
from bench.harness.traffic import Traffic

CHECKOUT = spec.ROOT.parent
# A traced run measures a shorter window: a trace grows with every
# device operation, and reading it has to fit in the run's time.
TRACE_WINDOW_S = 8.0


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Compiles:
    """Counts programs compiled (or fetched from the persistent cache)."""

    def __init__(self):
        self.count = 0
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        if "backend_compile" in name:
            self.count += 1

    def _on_event(self, name, **kw):
        if "cache_hits" in name:
            self.count += 1


def key_for(seed: int):
    """A threefry key from any non-negative seed, through NumPy's seeding."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def weights(cell: spec.Cell, seed: int):
    """The configuration's weights from the seed: one jitted call, on device."""
    import jax

    init = jax.jit(partial(cell.family.init_weights, model=cell.config["model"]))
    return jax.block_until_ready(init(key_for(seed)))


def build(cell: spec.Cell, w, devices):
    """The program under test, at the configuration's sizes, holding ``w``."""
    import jax

    from repro.configs.base import DecodePipelineConfig, SSMConfig
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.serve.engine import ServeConfig, StreamEngine

    over = cell.family.program_arch(cell.config["model"])
    if "ssm" in over:
        over["ssm"] = SSMConfig(**over["ssm"])
    arch = get_config(cell.config["arch"]).with_overrides(**over)
    params = cell.family.to_program(w)
    want = abstract_params(T.model_layout(arch))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise ValueError("weights do not match the program's parameter layout")
    serving = cell.config["serving"]
    scfg = ServeConfig(max_batch=serving["max_batch"], max_len=serving["max_len"],
                       prefill_chunk=serving["prefill_chunk"])
    pcfg = DecodePipelineConfig(**serving.get("pipeline", {}))
    mesh = None
    if cell.chips > 1:
        mesh = jax.make_mesh((cell.chips,), (pcfg.axis_name,), devices=devices,
                             axis_types=(jax.sharding.AxisType.Auto,))
    return StreamEngine(params, arch, scfg, pcfg, mesh=mesh)


def _source_vocab(cell: spec.Cell) -> int:
    return int(cell.config["model"]["vocab_size"])


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        root: Path = spec.ROOT, require_tpu: bool = True, cache: bool = True,
        control: bool = False) -> dict:
    """One run of cell ``name``.  ``require_tpu``, ``cache`` (the persistent
    compilation cache in the checkout) and ``control`` (compare the fp8
    control's choices in place of the served tokens, and keep the
    program's gap as ``program_gap``, for the study of the limit) exist
    for the tests and for ``bench/study``; ``bench/run.py`` keeps their
    defaults."""
    cell = spec.Cell(name, root)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    devices = devices[:cell.chips]
    if cache:
        (CHECKOUT / ".jax_cache").mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()

    w = weights(cell, seed)
    engine = build(cell, w, devices)
    del w
    serving = cell.config["serving"]
    session = serve.Session(engine)
    serve.warm_up(session, vocab=_source_vocab(cell), prefill_chunk=serving["prefill_chunk"],
                  admit_per_round=engine.pcfg.admit_per_round)
    traffic = Traffic(cell.mix, seed, vocab=_source_vocab(cell),
                      max_batch=serving["max_batch"], rate_per_s=cell.params.get("rate_per_s"))

    tracer = _Tracer(CHECKOUT / ".bench_tmp" / "trace") if trace else None
    marks = {}

    def on_open():
        marks["compiles"] = compiles.count
        if tracer:
            tracer.start()

    def on_close():
        if tracer:
            tracer.stop()
        marks["compiles"] = compiles.count - marks["compiles"]

    window_s = min(seconds, TRACE_WINDOW_S) if trace else seconds
    drive = serve.run_closed if traffic.loop == "closed" else serve.run_open
    win = drive(session, traffic, window_s, on_open=on_open, on_close=on_close)
    setup_s = win.t0 - t_start
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    attempted = len(win.attempted)
    failed = sum(r.failed for r in win.attempted)
    lateness = np.asarray(win.lateness) if win.lateness else np.zeros(1)
    log(f"window {win.seconds:.3f} s, {win.rounds} rounds, {attempted} requests "
        f"({failed} failed); compilations inside the window: {marks['compiles']}; "
        f"generator lateness p50 {1e3 * np.median(lateness):.3f} ms, "
        f"max {1e3 * lateness.max():.3f} ms")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        reading = _Reading(cell, engine, win, tracer, devices, peak)
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reading.trace.busy_s(), window_s=reading.trace.window_s)
        breakdown = reading.trace.breakdown()
        tracer.clean()
    else:
        values = serve.client_metrics(win)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if values.get(m["name"]) is not None}

    # The check: requests the window served, against the reference, once
    # the program's state is gone.
    picked = check.sample([r for r in win.records
                           if any(win.t0 < t <= win.t1 for t, _ in r.deliveries)], seed)
    prompts = [traffic.request(r.index).prompt for r in picked]
    served = [r.tokens for r in picked]
    del engine, session, win
    gc.collect()
    jax.clear_caches()
    limit = float(cell.params["logit_gap_limit"])
    result_extra = {}
    if picked:
        inputs, targets, mask = check.teacher_forced(served, prompts, serving["max_len"])
        gaps = check.logit_gaps(cell.family.__name__, cell.config["model"],
                                weights(cell, seed), inputs, targets, mask, control=control)
        gap = gaps["served"]
        if control:
            # The control takes the program's place: its gap is the one
            # compared, and the program's own is kept beside it.
            log(f"program_gap {gap!r}")
            result_extra = {"program_gap": gap}
            gap = gaps["control"]
        log(f"check: {len(picked)} requests, {gaps['tokens']} served tokens "
            f"against the fp32 reference")
    else:
        gap = None
        log("check: the window completed no request; nothing to compare")
    correct = gap is not None and gap <= limit
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result.update(result_extra)
    result["check"] = {"logit_gap": {"value": gap, "limit": limit}}
    log(f"logit_gap {gap!r} limit {limit!r}")
    return result


class _Tracer:
    """The profiler over the window, into a fixed directory of the checkout."""

    def __init__(self, path: Path):
        self.path = path
        self.clean()

    def start(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.path), profiler_options=options)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def clean(self):
        shutil.rmtree(self.path, ignore_errors=True)


class _Reading:
    """What a per-layer metric reader gets: the reduced trace of the
    window and the counts the host knows of the work done in it."""

    def __init__(self, cell, engine, win, tracer, devices, peak):
        from bench import peaks
        from bench.harness import trace

        self.cell = cell
        self.model = cell.config["model"]
        self.family = cell.family
        self.dims = cell.family.dims(self.model)
        self.chips = len(devices)
        self.peaks = peaks.peaks_for(devices[0].device_kind)
        self.memory_peak_bytes = peak
        self.trace = trace.load(tracer.path)
        self.round_steps = engine.pcfg.round_steps
        # Context (rows attended) of every token decoded in the window.
        kv = []
        for r in win.records:
            seen = 0
            for t, n in r.deliveries:
                if win.t0 < t <= win.t1:
                    g = np.arange(seen, seen + n)
                    kv.extend((r.prompt_len + g[g >= 1]).tolist())
                seen += n
        self.decoded_kv_lens = np.asarray(kv, np.int64)
