#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Refuses any JAX backend but ``tpu`` and fewer chips than the
cell asks for (exit 2, no result line). Builds the cell's pipeline, warms
the cell's own shapes (set-up), measures for ``--seconds``, reads the
device's peak memory, frees the program's state, runs the reference over
what the window produced and prints, as the last line of standard
output, ``{"correct", "attempted", "failed", "metrics", "device"[,
"breakdown"], "compared"}``. Everything else it learns goes on earlier
lines (JSON, one object a line) and, traced, under ``benchmark/out/``.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start: set-up is counted from here

import argparse
import json
import os
import shutil
import sys
import threading
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import loader  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402
from stats import CompileCounts  # noqa: E402

PLATFORM = "tpu"


class Ctx:
    """What one run knows; pipelines, generators and readers share it."""

    def __init__(self, cell, args):
        self.cell = cell
        self.config, self.traffic, self.limits = cell.config, cell.traffic, cell.limits
        self.pipeline = cell.pipeline
        self.seed, self.seconds, self.traced = args.seed, float(args.seconds), bool(args.trace)
        self.t0 = _T0
        self.counts = CompileCounts()
        self.trace = None
        self.window_t0 = None

    def note(self, **fields) -> None:
        print(json.dumps(fields, default=str), flush=True)


def die_with_thread(args) -> None:
    """The threaded pw.run() has no other way to fail the run."""
    traceback.print_exception(args.exc_type, args.exc_value, args.exc_traceback)
    print(f"benchmark: thread {args.thread.name if args.thread else '?'} died",
          file=sys.stderr, flush=True)
    os._exit(3)


def device_record(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """Traces ``trace_seconds`` of the window, from a thread of its own:
    starts once the generator has opened the window. Device operations
    and the harness's own spans only: with Python call tracing on (JAX's
    default) the host's per-row code ran a sixth slower and the serve
    path fell seconds behind (my chip runs, PR 24)."""

    def __init__(self, ctx, out_dir: str):
        import jax

        self.ctx, self.dir = ctx, out_dir
        self.interval = None
        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0
        self.options.host_tracer_level = 1
        self.thread = threading.Thread(target=self._run, name="bench-tracer", daemon=True)

    def warm(self) -> None:
        """The profiler's first start takes seconds: spend them in set-up."""
        import jax

        jax.profiler.start_trace(self.dir + "_warm", profiler_options=self.options)
        jax.profiler.stop_trace()
        shutil.rmtree(self.dir + "_warm", ignore_errors=True)

    def _run(self) -> None:
        import jax

        ctx = self.ctx
        while ctx.window_t0 is None:
            time.sleep(0.005)
        length = float(ctx.traffic["trace_seconds"])
        start = ctx.window_t0 + max(0.0, (ctx.seconds - length) / 2)
        time.sleep(max(0.0, start - time.monotonic()))
        jax.profiler.start_trace(self.dir, profiler_options=self.options)
        t_start = time.monotonic()
        time.sleep(length)
        t_end = time.monotonic()
        jax.profiler.stop_trace()
        self.interval = (t_start, t_end)


def judge(compared: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none is above."""
    table, ok = {}, True
    for name, value in compared.items():
        if name not in limits:
            raise loader.BenchmarkError(f"no limit for {name!r} in the cell's limits file")
        table[name] = {"value": value, "limit": limits[name]}
        ok = ok and (value == value) and value <= limits[name]
    return ok, table


def run_cell(cell, args, devices) -> dict:
    ctx = Ctx(cell, args)
    threading.excepthook = die_with_thread
    ctx.counts.install()
    ctx.peaks = peaks.lookup(devices[0].device_kind)
    generator = cell.generator

    cell.pipeline.build(ctx)
    generator.setup(ctx)
    tracer = None
    if ctx.traced:
        out_dir = os.path.join(HERE, "out", "trace")
        shutil.rmtree(out_dir, ignore_errors=True)
        tracer = Tracer(ctx, out_dir)
        tracer.warm()
    setup_s = time.monotonic() - _T0
    ctx.note(phase="setup_done", setup_s=setup_s, compile_counts=ctx.counts.counts)
    if tracer is not None:
        tracer.thread.start()
    ctx.counts.phase = "window"
    result = generator.window(ctx)
    ctx.counts.phase = "after"
    if tracer is not None:
        tracer.thread.join(timeout=120)
        if tracer.interval is None:
            raise loader.BenchmarkError("the traced stretch never ended")
        raw = trace_reduce.read_xplane(tracer.dir)
        ctx.trace = trace_reduce.reduce(raw)
        ctx.trace["interval"] = tracer.interval
        ctx.trace["window_s"] = tracer.interval[1] - tracer.interval[0]
        ctx.note(trace_layout=raw["layout"], module_runs=ctx.trace["module_runs"])
        shutil.rmtree(tracer.dir, ignore_errors=True)

    device = device_record(devices)
    device["memory_peak_bytes"] = memory_peak(devices)
    ctx.note(
        window_compile_requests=ctx.counts.requests("window"),
        compile_counts=ctx.counts.counts,
        encoder_shapes=sorted({r[2:] for r in ctx.tap.dispatches if r[1] == "window"}),
    )

    generator.collect(ctx)
    cell.pipeline.free_index(ctx)
    t_check = time.monotonic()
    checked = generator.check(ctx)
    correct, compared = judge(checked["compared"], ctx.limits["limits"])
    correct = correct and result["failed"] == 0 and result["attempted"] > 0
    ctx.note(check_seconds=round(time.monotonic() - t_check, 2), check_notes=checked["notes"])

    metrics = dict(result["metrics"])
    metrics["setup_s"] = setup_s
    wanted = cell.per_layer if ctx.traced else cell.end_to_end
    out = {}
    for entry in wanted:
        name = entry["name"]
        value = cell.reader(name)(ctx) if ctx.traced else metrics.get(name)
        if value is not None:
            out[name] = {"value": value, "unit": entry["unit"]}
    line = {
        "correct": bool(correct),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": out,
        "device": device,
    }
    if ctx.traced:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = {
            "device_ops": ctx.trace["device_ops"], "idle_gaps": ctx.trace["idle_gaps"],
        }
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = loader.Cell(loader.load(), args.workload)
    # every program, however quickly it compiled, goes into the cache
    # (inside the checkout: internals/device.py place_compile_cache)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    # libtpu's logs go under the checkout, not to its fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, "out", "tpu_logs"))
    import jax

    devices = jax.devices()
    print(
        f"benchmark: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)}",
        flush=True,
    )
    if jax.default_backend() != PLATFORM or len(devices) < cell.chips:
        print(
            f"benchmark: backend {jax.default_backend()!r} with {len(devices)} device(s); "
            f"the cell needs {cell.chips} x {PLATFORM!r} -- refusing",
            file=sys.stderr,
        )
        return 2
    try:
        line = run_cell(cell, args, devices[: cell.chips])
    except BaseException as failure:
        traceback.print_exc()
        print(f"benchmark: FAILED -- {failure!r}", file=sys.stderr, flush=True)
        return 1
    for name, row in line["compared"].items():
        print(f"compared {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: the server thread and the gateway's workers are daemons
    # with no stop handle; nothing they hold needs an orderly shutdown
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
