#!/usr/bin/env python3
"""The control of a cell: the reference put in the program's place, one
precision below what the configuration states, judged by the cell's own
comparison and limits. It has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds <run_seconds>

No server is built: the control is the reference, so the inputs (texts,
weights, fill) made from the seed are all it needs, at the cell's own
sizes: the cell's pipeline gives them (``control_inputs``) and its
generator puts the control in the program's place (``control``). Prints each compared number beside its limit, per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run  # sets the paths
import loader


def control_of(cell, seed: int, seconds: float) -> tuple[bool, dict]:
    """(correct, compared) of the control for one seed."""
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    ctx = run.Ctx(cell, args)
    cell.pipeline.control_inputs(ctx)
    gen = cell.generator
    gen.make_inputs(ctx)
    checked = gen.check(ctx, gen.control(ctx))
    return run.judge(checked["compared"], cell.limits["limits"])


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args()
    import jax

    if jax.default_backend() != run.PLATFORM:
        print("control: no TPU -- refusing", file=sys.stderr)
        return 2
    cell = loader.Cell(loader.load(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, compared = control_of(cell, seed, args.seconds)
        print(json.dumps({"control_seed": seed, "correct": correct, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
