"""Which share of the full slab a group of ``SentenceEncoder.encode`` should
hold, and how many rows at most: the encoder alone, on the chip, over
calls of 256 documents drawn from the ingest cells' own length law.

    chiprun -- python scripts/encoder_group_check.py [--models bge-base,bge-small]
        [--variants 1:256,32:128,32:64] [--calls 24]

For each model and each ``ENCODER_GROUP_SHARE:ENCODER_GROUP_ROWS`` (1:256
is the one slab a call was before calls were cut): every member of the
enumerated set alone (median ms of a dispatch waited for, and the seconds
its first dispatch took: a first meeting, cold in a call's first process
and from the compile cache in its second), then ``--calls`` calls end to
end after eight warm ones: wall ms a call, the ring's ``encoder.wait``
and ``encoder.tokenize`` ms a call, dispatches and padded tokens a call,
and the largest 1 - cos between a row under this variant and under the
first. Last, what the ladder costs a lone row between two rungs: 8 x 320
against 8 x 512, 8 x 16 against 8 x 32. One JSON line a reading. Refuses
any backend but the TPU: a CPU time is not a device time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--models", default="bge-base,bge-small")
    parser.add_argument("--variants", default="1:256,32:128,32:64")
    parser.add_argument("--calls", type=int, default=24)
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()

    import jax
    import numpy as np

    import corpus
    from pathway_tpu.internals import device as dev
    from pathway_tpu.internals import flight
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    if jax.default_backend() != "tpu":
        print(f"encoder_group_check: backend is {jax.default_backend()}, not tpu",
              file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmark", "traffic", "ingest-bulk.json")) as f:
        mix = json.load(f)
    warm = 8
    lengths = corpus.doc_lengths(
        (warm + args.calls) * 256, dict(mix["doc_words"], shuffle_within=256),
        mix["shape_seed"], args.seed,
    )
    calls = [
        corpus.texts(lengths[at:at + 256], args.seed, 1000 + at)
        for at in range(0, len(lengths), 256)
    ]
    table = []
    for model in args.models.split(","):
        cfg = {"bge-base": EncoderConfig.bge_base, "bge-small": EncoderConfig.bge_small}[model]()
        enc = SentenceEncoder(cfg, batch_size=256)
        dispatched = []

        def tapped(fn):
            def call(params, ids, second):
                dispatched.append(tuple(ids.shape))
                return fn(params, ids, second)

            return call

        enc._forward_compact = tapped(enc._forward_compact)
        one_slab = None
        first_s = {}

        def alone(rows, width):
            """Median ms of a dispatch of this shape, waited for."""
            ids = np.ones((rows, width), np.uint16)
            full = np.full((rows,), width, np.int32)
            took = []
            for _ in range(6):
                t0 = time.perf_counter()
                jax.block_until_ready(enc._forward_compact(enc.params, ids, full))
                took.append(time.perf_counter() - t0)
            first_s.setdefault(f"{rows}x{width}", round(took[0], 3))
            return 1e3 * statistics.median(took[1:])

        for variant in args.variants.split(","):
            share, most = (int(v) for v in variant.split(":"))
            dev.ENCODER_GROUP_SHARE, dev.ENCODER_GROUP_ROWS = share, most
            shapes = dev.encoder_group_shapes(256, cfg.max_len)
            members = {
                f"{rows}x{width}": alone(rows, width)
                for rows, width in (shapes[-1:] if share == 1 else shapes)
            }
            for texts in calls[:warm]:
                enc.encode(texts)
            dispatched.clear()
            lo = time.monotonic_ns()
            walls, out = [], []
            for texts in calls[warm:]:
                t0 = time.perf_counter()
                out.append(enc.encode(texts))
                walls.append(1e3 * (time.perf_counter() - t0))
            spans = flight.spans_between(lo, time.monotonic_ns())

            def ms_a_call(name):
                return sum(
                    s[flight.S_T1] - s[flight.S_T0] for s in spans
                    if s[flight.S_NAME] == name
                ) / 1e6 / len(walls)

            out = np.concatenate(out)
            if one_slab is None:
                one_slab = out
            row = {
                "model": model, "share": share, "most_rows": most,
                "group_tokens": 256 * cfg.max_len // share,
                "member_ms": members,
                "first_dispatch_s": {k: first_s[k] for k in members},
                "call_ms_median": statistics.median(walls),
                "call_ms_mean": statistics.fmean(walls),
                "wait_ms_a_call": ms_a_call("encoder.wait"),
                "tokenize_ms_a_call": ms_a_call("encoder.tokenize"),
                "dispatches_a_call": len(dispatched) / len(walls),
                "padded_tokens_a_call": sum(r * w for r, w in dispatched) / len(walls),
                "shapes": sorted(set(dispatched)),
                "max_gap_to_one_slab": float(np.max(1.0 - np.sum(out * one_slab, axis=1))),
            }
            table.append(row)
            print(json.dumps(row), flush=True)
        between = {f"8x{w}": alone(8, w) for w in (16, 32, 320, 512)}
        table.append({"model": model, "between_rungs_ms": between})
        print(json.dumps(table[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "encoder_group_check.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
