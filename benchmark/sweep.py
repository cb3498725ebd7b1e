#!/usr/bin/env python3
"""Find the highest question rate a serve cell sustains, once, on the chip.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --rates 10,20,30 --seconds 10

One set-up, then one open-loop stretch per rate. A rate is sustained when
the latency of the stretch's last quarter is not above that of its second
quarter by more than half (the backlog does not grow). Not part of a
check: the rate found is written into the mix's file as a number.
"""

from __future__ import annotations

import argparse
import sys
import time

import run  # sets the paths
import corpus
import loader
from stats import percentile


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args()
    args.trace = 0
    import jax

    if jax.default_backend() != run.PLATFORM:
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cell = loader.Cell(loader.load(), args.workload)
    ctx = run.Ctx(cell, args)
    ctx.counts.install()
    cell.pipeline.build(ctx)
    cell.generator.setup(ctx)
    ctx.note(setup_s=time.monotonic() - ctx.t0)
    gen = cell.generator
    for stretch, rate in enumerate(float(r) for r in args.rates.split(",")):
        n = int(rate * args.seconds)
        texts = gen._questions(ctx, n, 100 + stretch)
        due = corpus.arrivals(n, args.seconds, 7 + stretch)
        t0 = time.monotonic()
        records = gen._send(ctx, texts, due, t0)
        done = [r for r in records if r is not None and not r[4]]
        lat = [(r[2] - r[0]) * 1e3 for r in done]
        q = len(lat) // 4
        ctx.note(
            rate=rate, asked=n, answered=len(done),
            p50_ms=percentile(lat, 50), p95_ms=percentile(lat, 95),
            second_quarter_p50_ms=percentile(lat[q:2 * q], 50),
            last_quarter_p50_ms=percentile(lat[3 * q:], 50),
            drained_s=max(r[2] for r in done) - (t0 + args.seconds),
        )
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    os._exit(code)
