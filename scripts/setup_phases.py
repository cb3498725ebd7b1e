#!/usr/bin/env python
"""Where a benchmark process spends its set-up, by phase: never compare
two runs' ``setup_s`` totals, ``server_up`` (imports, weights) swings by
ten seconds between processes of one tree.

    python scripts/setup_phases.py LOG [LOG ...]

tabulates the ``phase`` notes of ``benchmark/run.py`` runs (one run's
standard output a file, or several runs after one another in a file):
``server_up``, then each later phase as the seconds since the one before
it (serve cells: ``filled``, ``docs_searchable``, ``setup_done``), the
compile requests of set-up, the run's end-to-end metrics.

    chiprun -- python scripts/setup_phases.py --workload <cell> --seed <n>

builds the cell and runs its set-up only (no window, no check), then
reads the span ring for every ``encoder.forward`` that met its shape for
the first time: when (seconds since process start), how long the
dispatch took, and what of that JAX reports as tracing, lowering and
handing to the backend (a cache hit's retrieval is inside the last);
beside them each shape's median later dispatch. One JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "of_it_retrieval_s",
}


def runs_of(lines) -> list:
    """One dict a run: its timed phase notes in order, its set-up's
    compile counts, its result line's metrics."""
    runs, run = [], None
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            note = json.loads(line)
        except ValueError:
            continue
        if note.get("phase") == "server_up":
            run = {"phases": [], "compiles": {}, "metrics": {}}
            runs.append(run)
        if run is None:
            continue
        if "phase" in note and ("seconds" in note or "setup_s" in note):
            run["phases"].append((note["phase"], note.get("seconds", note.get("setup_s"))))
            run["compiles"] = note.get("compile_counts", run["compiles"])
        if "metrics" in note and "correct" in note:
            run["correct"] = note["correct"]
            run["metrics"] = {k: v["value"] for k, v in note["metrics"].items()}
    return runs


def tabulate(paths: list) -> int:
    for path in paths:
        with open(path) as f:
            for run in runs_of(f):
                row, before = {"log": os.path.basename(path)}, 0.0
                for name, at in sorted(run["phases"], key=lambda p: p[1]):
                    row[name if not before else f"{name}-prev"] = round(at - before, 2)
                    before = at
                setup = run["compiles"].get("setup", {})
                row["setup_compile_requests"] = setup.get("compile_requests")
                row["setup_cache_hits"] = setup.get("cache_hits")
                row.update(correct=run.get("correct"), **run["metrics"])
                print(json.dumps(row))
    return 0


def measure(cell, args) -> dict:
    """Build ``cell`` and run its set-up; what the ring and JAX's own
    duration events say of every encoder shape's first dispatch."""
    import jax.monitoring

    import run
    from pathway_tpu.internals import flight

    timed = []  # (monotonic_ns at the event's end, field, seconds)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: event in EVENTS
        and timed.append((time.monotonic_ns(), EVENTS[event], secs))
    )
    args.trace, args.seconds = 0, 51.0
    ctx = run.Ctx(cell, args)
    ctx.counts.install()
    phases = []
    note = ctx.note

    def noted(**fields):
        if "phase" in fields:
            phases.append((fields["phase"], time.monotonic() - ctx.t0))
        note(**fields)

    ctx.note = noted
    cell.pipeline.build(ctx)
    cell.generator.setup(ctx)
    ctx.note(phase="setup_done", seconds=round(time.monotonic() - ctx.t0, 2),
             compile_counts=ctx.counts.counts)

    t0_ns = int(ctx.t0 * 1e9)  # monotonic and monotonic_ns are one clock
    forwards = [
        (s, flight.args_of(s)) for s in flight.spans_between(0, time.monotonic_ns())
        if s[flight.S_NAME] == "encoder.forward"
    ]
    later = {}
    for s, a in forwards:
        if not a["first"]:
            later.setdefault(a["bucket"], []).append((s[flight.S_T1] - s[flight.S_T0]) / 1e9)
    meetings = []
    for s, a in forwards:
        if not a["first"]:
            continue
        row = {
            "bucket": a["bucket"], "at_s": round((s[flight.S_T0] - t0_ns) / 1e9, 2),
            "dispatch_s": round((s[flight.S_T1] - s[flight.S_T0]) / 1e9, 3),
        }
        for at, field, secs in timed:
            if s[flight.S_T0] <= at <= s[flight.S_T1] + 1_000_000:
                row[field] = round(row.get(field, 0.0) + secs, 3)
        if a["bucket"] in later:
            row["later_dispatch_ms"] = round(1e3 * statistics.median(later[a["bucket"]]), 3)
        meetings.append(row)
    by_phase = {}
    for at, field, secs in timed:
        at_s = (at - t0_ns) / 1e9
        phase = next((name for name, end in phases if at_s <= end), "setup_done")
        row = by_phase.setdefault(phase, {})
        row[field] = round(row.get(field, 0.0) + secs, 3)
        if field == "backend_s":
            row["programs"] = row.get("programs", 0) + 1
    return {
        "workload": args.workload, "seed": args.seed,
        "phases": [(name, round(at, 2)) for name, at in phases],
        "first_meetings": meetings,
        "first_meetings_s": round(sum(m["dispatch_s"] for m in meetings), 2),
        "encoder_shapes": len(meetings),
        "jax_seconds_until_phase": by_phase,
        "compile_counts": ctx.counts.counts,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("logs", nargs="*")
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args()
    if not args.workload:
        return tabulate(args.logs)
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run  # the process's start, as the harness counts it
    import loader

    import jax

    if jax.default_backend() != run.PLATFORM:
        print("setup_phases: no TPU", file=sys.stderr)
        return 2
    print(json.dumps(measure(loader.Cell(loader.load(), args.workload), args)), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # the measured process holds daemon threads with no stop handle
