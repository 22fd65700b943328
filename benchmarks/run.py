"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only primes,...]
    PYTHONPATH=src python -m benchmarks.run --check [--check-tolerance 0.1]

Prints ``name,us_per_call,derived`` CSV.  quick mode (default) shrinks
problem sizes so the suite completes in minutes on one CPU core; --full
uses the paper's sizes (Table 1: primes to 20000/60000, Fateman ^20).

The pipeline suite additionally persists its (schedule x M) sweep —
modeled vs measured — to ``BENCH_pipeline.json`` at the repo root, the
perf-trajectory baseline future PRs diff against; the serve suite
persists ``BENCH_serve.json`` (tokens/sec + TTFT) and the train suite
``BENCH_train.json`` (value_and_grad step time per schedule x M,
autodiff vs planned backward).  ``--check`` is the enforcement: it
runs a fresh paired sweep, diffs every cell against the persisted
baselines (pipeline wall-clock, serve throughput, train wall-clock),
and exits nonzero if any cell regressed by more than
``--check-tolerance`` (default 10%) — the perf gate perf-sensitive PRs
run before merging.  ``--check --suite serve`` gates only the named
suite(s); a requested gate with no baseline exits 2 with the exact
``--suite`` command that creates one (never a KeyError).  ``--check``
does not overwrite the baselines; re-run without it to re-baseline
intentionally.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from benchmarks import (
    bench_chunking,
    bench_pipeline,
    bench_polymul,
    bench_primes,
    bench_roofline,
    bench_serve,
    bench_train,
)
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "primes": bench_primes,      # Table 1 / Fig 3
    "polymul": bench_polymul,    # Table 1 / Fig 4
    "chunking": bench_chunking,  # §7 proposal
    "pipeline": bench_pipeline,  # bubble model (DESIGN §2)
    "roofline": bench_roofline,  # §Roofline table from dry-run artifacts
    "serve": bench_serve,        # Stream-shaped serving (tok/s + TTFT)
    "train": bench_train,        # autodiff vs planned backward step time
}

_ROOT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)
BASELINE_PATH = os.path.join(_ROOT, "BENCH_pipeline.json")
SERVE_BASELINE_PATH = os.path.join(_ROOT, "BENCH_serve.json")
TRAIN_BASELINE_PATH = os.path.join(_ROOT, "BENCH_train.json")


def _cell_key(record: dict) -> tuple:
    """Identity of one sweep cell: compare like against like."""
    return (
        record["schedule"],
        record["devices"],
        record["interleave"],
        record["num_microbatches"],
        record["dim"],
        record["rows"],
    )


def _regressions(
    baseline: list[dict],
    fresh: list[dict],
    key_fn,
    metric: str,
    tolerance: float,
    higher_is_better: bool,
    report_fields: tuple[str, ...],
) -> list[dict]:
    """Generic directional gate: cells present in both sweeps whose
    ``metric`` moved the wrong way past ``tolerance``.  One compare
    loop serves wall-clock (lower is better) and throughput (higher is
    better) gates; pure so both are unit-testable offline."""
    base = {key_fn(r): r[metric] for r in baseline if metric in r}
    regressions = []
    for rec in fresh:
        if metric not in rec:
            continue
        key = key_fn(rec)
        if key not in base:
            continue
        before, after = base[key], rec[metric]
        bad = (
            after < before * (1.0 - tolerance)
            if higher_is_better
            else after > before * (1.0 + tolerance)
        )
        if bad:
            out = {f: rec[f] for f in report_fields if f in rec}
            out[f"baseline_{metric}"] = before
            out[f"measured_{metric}"] = after
            out["ratio"] = after / before
            regressions.append(out)
    return regressions


def check_regressions(
    baseline: list[dict], fresh: list[dict], tolerance: float
) -> list[dict]:
    """Pipeline cells whose measured wall-clock regressed past
    ``tolerance``.  Compares only cells present in both sweeps with
    identical problem sizes (so a --check quick run never diffs against
    a --full baseline)."""
    out = _regressions(
        baseline, fresh, _cell_key, "measured_seconds", tolerance,
        higher_is_better=False,
        report_fields=("schedule", "devices", "interleave", "num_microbatches"),
    )
    for r in out:  # keep the historical report-field names
        r["baseline_seconds"] = r.pop("baseline_measured_seconds")
        r["measured_seconds"] = r["measured_measured_seconds"]
        del r["measured_measured_seconds"]
    return out


def _serve_cell_key(record: dict) -> tuple:
    """Identity of one serve sweep cell.  ``kernels`` defaults to "xla"
    so baselines written before the kernel-dispatch axis existed keep
    gating the xla cells."""
    return (
        record.get("engine"),
        record.get("schedule"),
        record.get("devices"),
        record.get("interleave"),
        record.get("kernels", "xla"),
        record.get("batch"),
        record.get("dim"),
        record.get("max_new"),
    )


def check_serve_regressions(
    baseline: list[dict], fresh: list[dict], tolerance: float
) -> list[dict]:
    """Serve cells whose tokens/sec regressed past ``tolerance`` —
    the throughput-directional (higher is better) instance of the
    shared gate — plus the chaos invariants: every fresh ``chaos_*``
    cell must report ``requests_lost == 0`` and ``bitwise_equal``
    recovery.  The chaos check is absolute (fresh-run-only, no
    baseline needed): losing a request under fault injection is a
    correctness bug at any tolerance."""
    out = _regressions(
        baseline, fresh, _serve_cell_key, "tokens_per_sec", tolerance,
        higher_is_better=True, report_fields=("engine", "batch"),
    )
    for r in out:
        r["baseline_tok_s"] = r.pop("baseline_tokens_per_sec")
        r["measured_tok_s"] = r.pop("measured_tokens_per_sec")
    for rec in fresh:
        if "requests_lost" not in rec:
            continue
        if rec["requests_lost"] != 0 or rec.get("bitwise_equal") is False:
            out.append(
                {
                    "engine": rec.get("engine"),
                    "batch": rec.get("batch"),
                    "requests_lost": rec["requests_lost"],
                    "bitwise_equal": rec.get("bitwise_equal"),
                }
            )
    return out


def _train_cell_key(record: dict) -> tuple:
    """Identity of one train sweep cell (schedule x backward x M)."""
    return (
        record.get("schedule"),
        record.get("backward"),
        record.get("devices"),
        record.get("interleave"),
        record.get("num_microbatches"),
        record.get("dim"),
        record.get("rows"),
    )


def check_train_regressions(
    baseline: list[dict], fresh: list[dict], tolerance: float
) -> list[dict]:
    """Train-step cells whose wall-clock regressed past ``tolerance`` —
    the autodiff-vs-planned backward sweep instance of the shared
    gate."""
    out = _regressions(
        baseline, fresh, _train_cell_key, "measured_seconds", tolerance,
        higher_is_better=False,
        report_fields=("schedule", "backward", "num_microbatches"),
    )
    for r in out:
        r["baseline_seconds"] = r.pop("baseline_measured_seconds")
        r["measured_seconds"] = r.pop("measured_measured_seconds")
    return out


# The gated suites: (module, baseline path, cell-key fn, comparison fn,
# the metric a record must carry to be comparable, one-line regression
# formatter).  One table + one driver instead of a copy-pasted block
# per suite; adding a gate is adding a row.
GATES = {
    "pipeline": (
        lambda: bench_pipeline, BASELINE_PATH, _cell_key, check_regressions,
        "measured_seconds",
        lambda r: (
            f"# REGRESSION pipeline {r['schedule']} D={r['devices']} "
            f"V={r['interleave']} M={r['num_microbatches']}: "
            f"{r['baseline_seconds']*1e3:.2f}ms -> "
            f"{r['measured_seconds']*1e3:.2f}ms ({r['ratio']:.2f}x)"
        ),
    ),
    "serve": (
        lambda: bench_serve, SERVE_BASELINE_PATH, _serve_cell_key,
        check_serve_regressions, "tokens_per_sec",
        lambda r: (
            f"# CHAOS VIOLATION serve {r['engine']} b={r['batch']}: "
            f"requests_lost={r['requests_lost']} "
            f"bitwise_equal={r['bitwise_equal']}"
            if "requests_lost" in r
            else f"# REGRESSION serve {r['engine']} b={r['batch']}: "
            f"{r['baseline_tok_s']:.1f} -> {r['measured_tok_s']:.1f} "
            f"tok/s ({r['ratio']:.2f}x)"
        ),
    ),
    "train": (
        lambda: bench_train, TRAIN_BASELINE_PATH, _train_cell_key,
        check_train_regressions, "measured_seconds",
        lambda r: (
            f"# REGRESSION train {r['schedule']} {r['backward']} "
            f"M={r['num_microbatches']}: "
            f"{r['baseline_seconds']*1e3:.2f}ms -> "
            f"{r['measured_seconds']*1e3:.2f}ms ({r['ratio']:.2f}x)"
        ),
    ),
}


def _load_baseline(label: str, path: str) -> list | None:
    """Load one gate's persisted sweep, or explain exactly how to create
    it.  A missing file or a file without a ``sweep`` key (a corrupt or
    hand-edited baseline) both return None after printing the fix — the
    gate must never die with a KeyError."""
    if not os.path.exists(path):
        print(
            f"# --check {label}: no baseline at {path}; run "
            f"`python -m benchmarks.run --suite {label}` first",
            file=sys.stderr,
        )
        return None
    with open(path) as f:
        try:
            data = json.load(f)
        except json.JSONDecodeError as e:
            print(
                f"# --check {label}: unreadable baseline {path} ({e}); "
                f"re-run `python -m benchmarks.run --suite {label}`",
                file=sys.stderr,
            )
            return None
    sweep = data.get("sweep")
    if not isinstance(sweep, list):
        print(
            f"# --check {label}: baseline {path} has no 'sweep' list; "
            f"re-run `python -m benchmarks.run --suite {label}`",
            file=sys.stderr,
        )
        return None
    return sweep


def _run_gate(label: str, tolerance: float, full: bool) -> int:
    """Run one suite fresh and diff it against its persisted baseline.

    Returns 0 clean, 1 on regression, 2 when nothing was comparable
    (size mismatch between the fresh run and the baseline, or no usable
    baseline).
    """
    module_fn, path, key_fn, check_fn, metric, fmt = GATES[label]
    module = module_fn()
    baseline = _load_baseline(label, path)
    if baseline is None:
        return 2
    for row in module.run(quick=not full):
        print(row)
    fresh = getattr(module.run, "records", [])
    compared = {
        key_fn(r) for r in fresh if metric in r
    } & {key_fn(r) for r in baseline if metric in r}
    regressions = check_fn(baseline, fresh, tolerance)
    print(
        f"# --check {label}: {len(compared)} cells compared, "
        f"{len(regressions)} regressed beyond {tolerance:.0%}",
        file=sys.stderr,
    )
    # Violations outrank incomparability: a chaos cell losing requests
    # must fail the gate even when no throughput cell matched the
    # baseline (the chaos invariants are fresh-run-only).
    for r in regressions:
        print(fmt(r), file=sys.stderr)
    if regressions:
        return 1
    if not compared:
        print(
            f"# --check {label}: no comparable cells (size mismatch?)",
            file=sys.stderr,
        )
        return 2
    return 0


def run_check(tolerance: float, full: bool, only: str | None = None) -> int:
    """The perf gate.  ``only`` (from --only/--suite) restricts which
    gates run; an explicitly requested gate with no baseline is an error
    (rc 2) with a message naming the --suite run that creates it, while
    un-requested ride-along gates merely note the skip."""
    if only is not None:
        labels = [n for n in only.split(",") if n]
        unknown = [n for n in labels if n not in GATES]
        if unknown:
            print(
                f"# --check: no gate for suite(s) {unknown}; gated suites "
                f"are {list(GATES)}",
                file=sys.stderr,
            )
            return 2
    else:
        labels = list(GATES)
    # Every requested gate runs — one incomparable baseline must not
    # mask a real regression in a later suite.  Regression (1) outranks
    # incomparability (2) in the aggregate exit code.
    rcs = []
    for label in labels:
        if (
            only is None
            and label != "pipeline"
            and not os.path.exists(GATES[label][1])
        ):
            # Ride-along gates only gate once baselined — but say so.
            print(
                f"# --check {label}: skipped (no baseline; run "
                f"`python -m benchmarks.run --suite {label}` to start "
                "gating it)",
                file=sys.stderr,
            )
            continue
        rcs.append(_run_gate(label, tolerance, full))
    if 1 in rcs:
        return 1
    if 2 in rcs or not rcs:
        return 2
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    ap.add_argument(
        "--suite", default=None,
        help="alias of --only (e.g. --suite serve)",
    )
    ap.add_argument(
        "--check",
        action="store_true",
        help="diff a fresh pipeline sweep against BENCH_pipeline.json and "
        "exit nonzero on wall-clock regression (the perf gate)",
    )
    ap.add_argument(
        "--check-tolerance",
        type=float,
        default=0.10,
        help="relative slowdown tolerated per sweep cell (default 0.10)",
    )
    args = ap.parse_args()
    use_compile_cache()

    if args.check:
        raise SystemExit(
            run_check(args.check_tolerance, args.full, args.only or args.suite)
        )

    only = args.only or args.suite
    names = only.split(",") if only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            rows = SUITES[name].run(quick=not args.full)
            for row in rows:
                print(row)
            sys.stdout.flush()
            if name in GATES:
                _write_baseline(
                    GATES[name][1], getattr(SUITES[name].run, "records", [])
                )
        except Exception as e:  # noqa: BLE001
            failed.append((name, e))
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmark suites failed: {[n for n, _ in failed]}")


def _write_baseline(path: str, records: list) -> None:
    if not records:
        return
    payload = {"sweep": records}
    try:
        import subprocess

        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=_ROOT, timeout=10, stdin=subprocess.DEVNULL,
        )
        if sha.returncode == 0:
            payload["git_sha"] = sha.stdout.strip()
    except OSError:
        pass  # not a git checkout / git unavailable: baseline still valid
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
