"""From a profiler trace to busy and idle seconds, time per executable,
the dearest device operations and the longest idle gaps.

``read_xplane`` turns an ``.xplane.pb`` into plain lists; everything else
works on those lists (tests feed it synthetic ones). Times are seconds.

* device planes: ``/device:TPU:<n>``; line ``XLA Ops`` gives the operation
  intervals (busy = their union), line ``XLA Modules`` one interval per
  executable run, named ``jit_<function>(<fingerprint>)``;
* host spans: events named ``bench.*`` (the harness's own
  ``TraceAnnotation``s) on any host plane.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."


def read_xplane(trace_dir: str) -> dict:
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: dict[str, dict[str, list]] = {}
    spans: list[tuple[str, float, float]] = []
    layout: dict[str, list[str]] = {}
    for plane in data.planes:
        layout[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    target = dev["ops"]
                elif line.name == "XLA Modules":
                    target = dev["modules"]
                else:
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    target.append((ev.name, start, start + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = ev.start_ns * 1e-9
                        spans.append((ev.name, start, start + ev.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans, "layout": layout}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in union((s, e) for _, s, e in events))


def module_name(raw: str) -> str:
    """``jit_search(1234567)`` -> ``jit_search``."""
    return re.sub(r"\(.*$", "", raw).strip()


def op_name(raw: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the trace
    names an operation by its whole HLO line."""
    return raw.split(" = ", 1)[0].lstrip("%")[:96]


def time_by_name(events, name_of=lambda n: n) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, start, end in events:
        key = name_of(name)
        out[key] = out.get(key, 0.0) + (end - start)
    return out


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] in which no event ran."""
    gaps, at = [], lo
    for start, end in union((s, e) for _, s, e in events):
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, end)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def attribute(gap: tuple[float, float], spans) -> str:
    """The harness span covering most of the gap, or what it means that
    none does."""
    best, best_cover = "outside-harness-spans", 0.0
    for name, start, end in spans:
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (mean over the device planes), the traced span of device
    activity, seconds per executable, and the two ``breakdown`` lists."""
    devices = trace["devices"]
    if not devices:
        return {"busy_s": 0.0, "per_module_s": {}, "module_runs": {}, "device_ops": [],
                "idle_gaps": [], "first_s": None, "last_s": None}
    busy, per_module, per_op, runs = [], {}, {}, {}
    first = min((s for d in devices.values() for _, s, _ in d["ops"] or d["modules"]), default=None)
    last = max((e for d in devices.values() for _, _, e in d["ops"] or d["modules"]), default=None)
    gaps_by_what: dict[str, float] = {}
    for dev in devices.values():
        events = dev["ops"] or dev["modules"]
        busy.append(busy_seconds(events))
        for name, secs in time_by_name(dev["modules"], module_name).items():
            per_module[name] = per_module.get(name, 0.0) + secs / len(devices)
        for raw, start, end in dev["modules"]:
            n, s = runs.get(module_name(raw), (0, 0.0))
            runs[module_name(raw)] = (n + 1, s + (end - start))
        for name, secs in time_by_name(events, op_name).items():
            per_op[name] = per_op.get(name, 0.0) + secs / len(devices)
        if first is not None:
            for gap in idle_gaps(events, first, last):
                what = attribute(gap, trace["spans"])
                gaps_by_what[what] = gaps_by_what.get(what, 0.0) + (gap[1] - gap[0]) / len(devices)
    ranked = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": sum(busy) / len(busy),
        "per_module_s": per_module,
        "module_runs": runs,
        "device_ops": ranked(per_op),
        "idle_gaps": ranked(gaps_by_what),
        "first_s": first,
        "last_s": last,
    }


# -- what the per-layer readers share -------------------------------------------------


def module_runs(ctx, which: str) -> tuple[int, float]:
    """(runs, device seconds) in the traced stretch of the executables
    whose name matches the configuration's ``trace_modules[which]``."""
    pattern = re.compile(ctx.config["trace_modules"][which])
    runs, secs = 0, 0.0
    for name, (n, s) in ctx.trace.get("module_runs", {}).items():
        if pattern.search(name):
            runs, secs = runs + n, secs + s
    return runs, secs


def in_trace(ctx, rows, phase_at: int = 1):
    """Tap rows recorded inside the traced stretch of the window."""
    lo, hi = ctx.trace["interval"]
    return [r for r in rows if r[phase_at] == "window" and lo <= r[0] <= hi]


def idle_share(ctx):
    """100 x (1 - union of device-operation intervals / traced stretch),
    or nothing where no operation ran in the trace."""
    if not ctx.trace or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
