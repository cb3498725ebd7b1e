#!/usr/bin/env python3
"""Which share of its capacity an answer cell (``--workload``) can be
offered: one set-up a seed, then the cell's own window (its fixed schedule, its questions, its
``--seconds``) once at each rate, and last, if asked, one window above
capacity whose completions a second are the capacity itself.

    chiprun -- python scripts/answer_rate_check.py [--workload CELL] --seed <n> \
        --rates 2.0,1.7,1.4 [--over 3.6] [--seconds 51]

Run it on six seeds and hold each rate's ``query_p50_ms`` /
``query_p95_ms`` spread against half its bound (``--spread`` does that
over the files of earlier runs). The rate found is written into
the cell's mix (``benchmark/traffic/``) as a number; no check runs this.
Each run leaves ``chiprun_out/rate_check/<cell>/<seed>.json``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
OUT = os.path.join(REPO, "chiprun_out", "rate_check")


def spread(values: list, drop_farthest: bool = False) -> float:
    """Quartile distance over the median, as the driver reckons it."""
    if drop_farthest:
        mid = statistics.median(values)
        values = sorted(values, key=lambda v: abs(v - mid))[:-1]
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def report(cell: str) -> int:
    runs = [json.load(open(p)) for p in sorted(glob.glob(os.path.join(OUT, cell, "*.json")))]
    rates = sorted({w["rate"] for r in runs for w in r["windows"] if not w["over"]})
    for rate in rates:
        rows = [w for r in runs for w in r["windows"] if w["rate"] == rate and not w["over"]]
        line = {"rate": rate, "seeds": len(rows)}
        for name in ("p50_ms", "p95_ms", "generate_ms_p50", "busy_share"):
            values = [w[name] for w in rows]
            line[name] = [round(v, 2) for v in values]
            if len(values) >= 4:
                line[name + "_spread"] = round(spread(values), 4)
                line[name + "_spread_less_farthest"] = round(spread(values, True), 4)
        print(json.dumps(line))
    for r in runs:
        for w in r["windows"]:
            if w["over"]:
                print(json.dumps({"seed": r["seed"], "over": w}))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="granite-4.0-h-small.answer-steady")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", default="")
    parser.add_argument("--over", type=float, default=0.0)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--spread", action="store_true")
    args = parser.parse_args()
    if args.spread:
        return report(args.workload)
    args.trace = 0

    import run  # sets the paths
    import loader

    import jax

    if jax.default_backend() != run.PLATFORM:
        print("answer_rate_check: no TPU", file=sys.stderr)
        return 2
    windows = offer(loader.Cell(loader.load(), args.workload), args)
    os.makedirs(os.path.join(OUT, args.workload), exist_ok=True)
    with open(os.path.join(OUT, args.workload, f"{args.seed}.json"), "w") as f:
        json.dump({"seed": args.seed, "seconds": args.seconds, "windows": windows}, f)
    return 0


def offer(cell, args) -> list:
    """Set the cell up once, then offer it each rate for a window."""
    import run
    import corpus
    from stats import percentile

    ctx = run.Ctx(cell, args)
    ctx.counts.install()
    cell.pipeline.build(ctx)
    gen = cell.generator
    gen.setup(ctx)
    ctx.note(setup_s=time.monotonic() - ctx.t0)
    tap, k = ctx.answer_tap, int(ctx.traffic["k"])
    rates = [(float(r), False) for r in args.rates.split(",") if r]
    if args.over:
        rates.append((args.over, True))
    windows = []
    for rate, over in rates:
        n = int(round(rate * args.seconds))
        texts = gen._questions(ctx, n, 31)
        topics = gen._asked(ctx, n, 31)[1]
        due = corpus.arrivals(n, args.seconds, ctx.traffic["shape_seed"])
        ctx.tap.phase = ctx.counts.phase = f"rate{rate}"
        t0 = time.monotonic()
        records = gen._send(ctx, texts, due, t0)
        done = [(r, g) for r, g in zip(records, topics) if r is not None and not r[4]]
        lat = [(r[2] - r[0]) * 1e3 for r, _ in done]
        calls = [c for c in tap.calls if c[2] == ctx.tap.phase]
        last = max(r[2] for r, _ in done) - t0
        windows.append({
            "rate": rate, "over": over, "asked": n, "answered": len(done),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "generate_calls": len(calls), "prompts": sum(c[3] for c in calls),
            "generate_ms_p50": percentile([(c[1] - c[0]) * 1e3 for c in calls], 50),
            "busy_share": sum(c[1] - c[0] for c in calls) / last,
            "prompt_tokens_p50": percentile([c[4] / c[3] for c in calls], 50),
            "prompt_tokens_min_max": [min(c[4] / c[3] for c in calls),
                                      max(c[4] / c[3] for c in calls)],
            "own_topic_share": sum(
                {gen._doc_id(d) // k for d in r[3]["context_docs"]} == {int(g)}
                for r, g in done) / len(done),
            "last_reply_s": last, "completed_per_s": len(done) / last,
            "compile_requests": ctx.counts.requests(),
        })
        ctx.note(**windows[-1])
        time.sleep(1.0)
    return windows


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
