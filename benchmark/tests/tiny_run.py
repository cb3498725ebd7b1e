"""One tiny run on the CPU with the timed path broken underneath (or
not): prints the result line. Started by test_faults.py, one process a
run (a pipeline lives as long as its process)."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny import tiny_cell, run  # noqa: E402

parser = argparse.ArgumentParser()
parser.add_argument("--workload", required=True)
parser.add_argument("--fault", default="none")
args = parser.parse_args()
cell = tiny_cell(args.workload)

if args.fault == "state_unchanged":
    # the index write returns its state as it got it
    import pathway_tpu.ops.knn as knn

    knn._write_slots = lambda vectors, valid, sq_norms, *a, **k: (vectors, valid, sq_norms)
elif args.fault == "embedding_altered":
    # what the encoder produces is altered where it is produced
    import numpy as np
    from pathway_tpu.models.encoder import SentenceEncoder

    inner = SentenceEncoder.encode_tokens_device

    def altered(self, ids, mask):
        out = np.asarray(inner(self, ids, mask)).copy()
        out[:, 0] += 0.2
        return out

    SentenceEncoder.encode_tokens_device = altered
elif args.fault == "answer_altered":
    # an answer is altered where it is produced: the best hit is dropped
    from pathway_tpu.ops.knn import KnnShard

    inner_search = KnnShard.search

    def search(self, queries, k):
        return [hits[1:] for hits in inner_search(self, queries, k + 1)]

    KnnShard.search = search

import jax  # noqa: E402

ns = argparse.Namespace(workload=args.workload, seed=5, seconds=3.0, trace=0)
try:
    line = run.run_cell(cell, ns, jax.devices()[:1])
except BaseException as failure:  # as run.main does: no result line, another exit code
    print(f"benchmark: FAILED -- {failure!r}", file=sys.stderr, flush=True)
    sys.stderr.flush()
    os._exit(1)
print(json.dumps(line), flush=True)
sys.stdout.flush()
os._exit(0)
