#!/usr/bin/env python
"""The span ring's clock against the device trace's, on the chip.

    chiprun -- python scripts/span_clock_check.py --workload <cell> --seed <n> [--seconds 51]

Makes ONE traced run of a benchmark cell in this process (calls
``benchmark/run.py``'s ``main``; its result line is printed as always),
keeps the ``.xplane.pb`` the run would delete (``trace_reduce.read_xplane``
is wrapped here to copy the directory out first; no benchmark file is
edited), and checks what sink 2 of the ring (internals/flight.py)
promises:

1. the program's spans are in the trace: ``pw.*`` events on a host plane,
   each ``pw.encoder.encode`` / ``pw.index.*`` nested inside the harness's
   ``bench.encoder.encode`` / ``bench.index.*`` on the same thread;
2. one clock: the offset between a span's ring stamps
   (``time.monotonic_ns``) and its xplane stamps stays within 100 us over
   the stretch;
3. every device idle gap over 1 ms is put down to the innermost span that
   covers it: by the ``pw.*`` events of the engine's thread, and, finer,
   by the ring's own spans moved onto the trace's clock (``engine.node``
   and the main loop's waits are in the ring only).

4. what a commit of documents costs the engine's thread: the steps of
   the stretch, ``json_hashes`` and ``array_copies`` a step and every
   node's self time (``commits`` in the report);
5. what a prefill chunk's latent attention went over: the chunks of the
   stretch, the cached-row ``blocks`` a chunk, and the ``kernel`` the
   ``answer.prefill`` site's first dispatch says its attention was
   lowered by; under an indexer the pairs a layer that owns one ``scored``
   and the pairs a layer attended after the choice (``selected``), over
   the stretch's chunks and decode steps, and what the first dispatch
   says chose and attended them (``prefill`` in the report); and the
   grouped product of the routed experts (``experts``: ``pallas`` or
   ``ragged_dot``) each answer site's first dispatch says its program
   took, the prefill's and decode's by bucket (``prefill.experts``).
6. what closed the gateway's batch windows: the stretch's windows by the
   ``closed`` arg of their ``gateway.queue`` spans (``idle``,
   ``answered``, ``timer``, ``full``), as shares of the windows, with the
   mean members a window of each (``window_closes`` in the report).

Prints one JSON object (also under ``chiprun_out/span_clock/``). Exit 0
when 1 and 2 hold, 1 when not, the run's own code when the run failed.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

GAP_NS = 1_000_000          # idle gaps worth a name
OFFSET_SPREAD_NS = 100_000  # the ring's and the trace's clocks agree this far
NEST_SLACK_NS = 50_000      # a pw.* event inside its bench.* span, to this

HARNESS_PARENT = {
    "pw.encoder.encode": "bench.encoder.encode",
    "pw.index.search": "bench.index.search",
    "pw.index.add_batch": "bench.index.add_batch",
    "pw.index.add": "bench.index.add",
}


def read_planes(trace_dir: str):
    """(host events by line, device op intervals) from the kept trace: a
    host event is (name, start_ns, end_ns); a line is a thread."""
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(paths[-1])
    host: dict[str, list] = {}
    ops: list[tuple[int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        start = int(ev.start_ns)
                        ops.append((start, start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):  # thread names repeat
                events = [
                    (ev.name, int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(("pw.", "bench."))
                ]
                if events:
                    host[f"{plane.name}/{line.name}#{i}"] = events
    return host, ops


def find_offset(host, ring):
    """xplane ns minus ring ns. Durations are the fingerprint: among spans
    of a millisecond or more, each (event, span) pair of one name whose
    durations agree to 20 us votes for an offset; the fullest 1 ms bin
    wins."""
    by_name = collections.defaultdict(list)
    for s in ring:
        if s[3] - s[2] >= 1_000_000:
            by_name["pw." + s[1]].append(s)
    votes = collections.Counter()
    for events in host.values():
        for name, start, end in events:
            for s in by_name.get(name, ()):
                if abs((end - start) - (s[3] - s[2])) < 20_000:
                    votes[(start - s[2]) // 1_000_000] += 1
    if not votes:
        return None
    (best, _), = votes.most_common(1)
    near = [
        start - s[2]
        for events in host.values() for name, start, end in events
        for s in by_name.get(name, ())
        if abs((end - start) - (s[3] - s[2])) < 20_000
        and abs((start - s[2]) // 1_000_000 - best) <= 1
    ]
    return sorted(near)[len(near) // 2]


def match(host, ring, offset):
    """Each ``pw.*`` event with the ring span of its name that starts
    nearest (within 200 us) once moved by ``offset``: (offsets, unmatched)."""
    starts = collections.defaultdict(list)
    for s in ring:
        starts["pw." + s[1]].append(s[2])
    for v in starts.values():
        v.sort()
    offsets, unmatched = [], collections.Counter()
    for events in host.values():
        for name, start, _ in events:
            if not name.startswith("pw."):
                continue
            mine = starts.get(name, ())
            at = bisect.bisect_left(mine, start - offset)
            near = [mine[i] for i in (at - 1, at) if 0 <= i < len(mine)]
            best = min(near, key=lambda t: abs(start - offset - t), default=None)
            if best is None or abs(start - offset - best) > 200_000:
                unmatched[name] += 1
            else:
                offsets.append((start, start - best))
    return offsets, unmatched


def nesting(host):
    """How many pw.* events lie inside the harness span they belong under."""
    found = collections.Counter()
    for events in host.values():
        outer = collections.defaultdict(list)
        for name, start, end in events:
            if name.startswith("bench."):
                outer[name].append((start, end))
        for name, start, end in events:
            want = HARNESS_PARENT.get(name)
            if want is None:
                continue
            inside = any(
                a - NEST_SLACK_NS <= start and end <= b + NEST_SLACK_NS
                for a, b in outer.get(want, ())
            )
            found[f"{name} inside {want}" if inside else f"{name} OUTSIDE {want}"] += 1
    return dict(found)


def innermost_timeline(spans):
    """Properly nested (name, start, end) spans of one thread -> sorted,
    disjoint (start, end, innermost name) segments."""
    out = []
    stack: list[tuple[str, int, int]] = []
    at = None

    def emit(until):
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][0]))
        at = until

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= start:
            emit(stack[-1][2])
            stack.pop()
        if stack:
            emit(start)
        at = start
        stack.append((name, start, min(end, stack[-1][2]) if stack else end))
    while stack:
        emit(stack[-1][2])
        stack.pop()
    return out


def idle_by_span(gaps, timeline, outside="outside every span"):
    """Seconds of the gaps by the timeline's innermost span."""
    secs = collections.Counter()
    starts = [seg[0] for seg in timeline]
    for a, b in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(timeline) and timeline[i][0] < b:
            s, e, name = timeline[i]
            cut = min(e, b) - max(s, a)
            if cut > 0:
                secs[name] += cut
                covered += cut
            i += 1
        secs[outside] += (b - a) - covered
    return {k: v * 1e-9 for k, v in secs.most_common() if v > 0}


def commits(ring, top: int = 12):
    """The stretch's steps that ran at least ten recorded nodes (a commit
    of documents, not a lone question): how many, their median length,
    ``json_hashes`` and ``array_copies`` a step (each None from a tree
    whose step does not say) and the nodes' median self ms a commit."""
    from pathway_tpu.internals import flight

    beneath = collections.Counter()
    for s in ring:
        beneath[s[5]] += s[3] - s[2]
    nodes = collections.defaultdict(list)
    for s in ring:
        if s[1] == "engine.node":
            nodes[s[6]].append((flight.args_of(s).get("label"), s[3] - s[2] - beneath[s[0]]))
    steps = [s for s in ring if s[1] == "engine.step" and len(nodes[s[6]]) >= 10]
    if not steps:
        return None
    by_label = collections.defaultdict(list)
    for s in steps:
        for label, ns in nodes[s[6]]:
            by_label[label].append(ns * 1e-6)

    def per_step(arg):
        n = [flight.args_of(s).get(arg) for s in steps]
        return None if None in n else {"min": min(n), "median": statistics.median(n), "max": max(n)}

    ms = {k: statistics.median(v) for k, v in by_label.items() if len(v) * 2 > len(steps)}
    return {
        "steps": len(steps),
        "step_ms_median": statistics.median([(s[3] - s[2]) * 1e-6 for s in steps]),
        "json_hashes": per_step("json_hashes"),
        "array_copies": per_step("array_copies"),
        "nodes_self_ms_sum": sum(ms.values()),
        "node_self_ms_median": dict(sorted(ms.items(), key=lambda kv: -kv[1])[:top]),
    }


def window_closes(ring, lo, hi):
    """The gateway's batch windows closed in the stretch, by what closed
    them (the ``closed`` arg of their members' ``gateway.queue`` spans:
    ``idle``, ``answered``, ``timer``, ``full``): how many, each cause's
    share of them and its mean members a window. None where no window
    closed, or where the spans do not say (a tree before the arg)."""
    from pathway_tpu.internals import flight

    members = collections.Counter()
    cause = {}
    for s in ring:
        if s[1] == "gateway.queue" and lo <= s[3] <= hi:
            args = flight.args_of(s)
            window = (args.get("window"), s[3])  # a window's members share its close
            members[window] += 1
            cause[window] = args.get("closed")
    if not cause or None in cause.values():
        return None
    by = collections.Counter(cause.values())
    return {
        "windows": len(cause),
        "share": {k: n / len(cause) for k, n in by.most_common()},
        "members_mean": {
            k: sum(members[w] for w, c in cause.items() if c == k) / n
            for k, n in by.most_common()
        },
    }


def prefill_chunks(ring, hi):
    """What the stretch's ``answer.prefill`` spans say of a chunk's latent
    attention: how many chunks, the cached-row blocks a chunk went over a
    layer (``blocks``: None from a tree whose span does not say), and
    which lowering the site's first dispatch met (``kernel``, and under an
    indexer ``selection`` and ``attention``; that dispatch is set-up's, so
    it is looked for in all the ring still holds), which grouped product
    the experts took (``experts``: the prefill's, and decode's by bucket,
    from the two sites' first dispatches); under an indexer the
    (query, cached position) pairs scored and selected, chunks and decode
    steps apart. None for a cell that prefills nothing."""
    from pathway_tpu.internals import flight

    chunks = [flight.args_of(s) for s in ring if s[1] == "answer.prefill"]
    if not chunks:
        return None
    blocks = [a.get("blocks") for a in chunks]
    firsts = [(s[1], flight.args_of(s)) for s in flight.spans_between(0, hi)
              if flight.args_of(s).get("first")]
    first = [a for _, a in firsts if "kernel" in a]
    out = {
        "chunks": len(chunks),
        "blocks": None if None in blocks
        else {"min": min(blocks), "mean": statistics.mean(blocks), "max": max(blocks)},
        "kernel": first[0]["kernel"] if first else None,
        "experts": {
            "prefill": next((a["experts"] for name, a in firsts
                             if name == "answer.prefill" and "experts" in a), None),
            "decode": {a["bucket"]: a["experts"] for name, a in firsts
                       if name == "answer.decode.step" and "experts" in a},
        },
    }
    if chunks and all("scored" in a for a in chunks):
        steps = [flight.args_of(s) for s in ring if s[1] == "answer.decode.step"]
        out.update(
            scored=sum(a["scored"] for a in chunks), selected=sum(a["selected"] for a in chunks),
            decode_steps=len(steps), decode_positions=sum(a["positions"] for a in steps),
            decode_selected=sum(a.get("selected", 0) for a in steps),
            selection=first[0].get("selection") if first else None,
            attention=first[0].get("attention") if first else None)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    args = parser.parse_args()

    import run  # benchmark/run.py
    import trace_reduce

    kept: dict = {}
    keep_dir = os.path.join(BENCH, "out", "trace_kept")
    read_inner, reduce_inner = trace_reduce.read_xplane, trace_reduce.reduce

    def read_and_keep(trace_dir):
        shutil.rmtree(keep_dir, ignore_errors=True)
        shutil.copytree(trace_dir, keep_dir)
        return read_inner(trace_dir)

    def reduce_and_keep(raw, *a, **k):
        kept["trace"] = reduce_inner(raw, *a, **k)  # run.py adds the interval to it
        return kept["trace"]

    trace_reduce.read_xplane, trace_reduce.reduce = read_and_keep, reduce_and_keep
    code = run.main([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "1",
    ])
    if code != 0 or "trace" not in kept:
        return code or 1

    from pathway_tpu.internals import flight

    lo_s, hi_s = kept["trace"]["interval"]
    lo, hi = int(lo_s * 1e9), int(hi_s * 1e9)
    ring = flight.spans_between(lo, hi)
    host, ops = read_planes(keep_dir)
    shutil.rmtree(keep_dir, ignore_errors=True)
    report: dict = {
        "workload": args.workload, "seed": args.seed, "stretch_s": hi_s - lo_s,
        "ring_spans_in_stretch": len(ring),
        "host_lines": {k: len(v) for k, v in host.items()},
        "pw_events": sum(1 for v in host.values() for e in v if e[0].startswith("pw.")),
        "nesting": nesting(host),
    }
    ok = report["pw_events"] > 0 and not any("OUTSIDE" in k for k in report["nesting"])
    offset = find_offset(host, ring)
    if offset is None:
        ok = False
        report["clock"] = "no span of a millisecond matched by duration"
    else:
        offsets, unmatched = match(host, ring, offset)
        values = sorted(o for _, o in offsets)
        spread = values[-1] - values[0] if values else None
        first = sorted(offsets)[: max(1, len(offsets) // 10)]
        last = sorted(offsets)[-max(1, len(offsets) // 10):]
        report["clock"] = {
            "matched": len(values), "unmatched": dict(unmatched),
            "offset_median_ns": values[len(values) // 2] if values else None,
            "offset_spread_ns": spread,
            "offset_p01_p99_ns": [values[len(values) // 100], values[-1 - len(values) // 100]]
            if values else None,
            "drift_first_to_last_tenth_ns":
                (sum(o for _, o in last) / len(last) - sum(o for _, o in first) / len(first))
                if values else None,
        }
        ok = ok and spread is not None and spread <= OFFSET_SPREAD_NS
    if ops and offset is not None:
        first, last = min(a for a, _ in ops), max(b for _, b in ops)
        gaps = [
            g for g in trace_reduce.idle_gaps([(None, a, b) for a, b in ops], first, last)
            if g[1] - g[0] >= GAP_NS
        ]
        engine_line = max(
            host, key=lambda k: sum(1 for e in host[k] if e[0] == "pw.engine.step"), default=None
        )
        by_pw = idle_by_span(
            gaps, innermost_timeline([e for e in host.get(engine_line, ()) if e[0].startswith("pw.")]),
            outside="outside pw.* spans of the engine's thread",
        )
        steps = collections.Counter(s[4] for s in ring if s[1] == "engine.step")
        thread = steps.most_common(1)[0][0] if steps else None
        labelled = [
            ((f"engine.node {flight.args_of(s).get('label')}" if s[1] == "engine.node" else s[1]),
             s[2] + offset, s[3] + offset)
            for s in ring if s[4] == thread
        ]
        report["device_idle"] = {
            "busy_to_busy_s": (last - first) * 1e-9,
            "gaps_over_1ms": len(gaps),
            "gaps_over_1ms_s": sum(b - a for a, b in gaps) * 1e-9,
            "by_innermost_pw_event_s": by_pw,
            "by_innermost_ring_span_s": idle_by_span(gaps, innermost_timeline(labelled)),
        }
    report["commits"] = commits(ring)
    report["window_closes"] = window_closes(ring, lo, hi)
    report["prefill"] = prefill_chunks(ring, hi)
    report["ok"] = bool(ok)
    text = json.dumps(report)
    print(text, flush=True)
    out_dir = os.path.join(REPO, "chiprun_out", "span_clock")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.workload}.seed{args.seed}.json"), "w") as f:
        f.write(text + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    # os._exit, as benchmark/run.py: the server thread and the gateway's
    # workers are daemons with no stop handle
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
