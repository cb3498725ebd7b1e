"""One tiny run of the long-context answer cell on the CPU with the timed
path broken underneath (or not): prints the result line. Started by
test_answer_long_cell.py, one process a run."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_answer_long import CELL, tiny_answer_long_cell  # noqa: E402
from tiny import run  # noqa: E402

parser = argparse.ArgumentParser()
parser.add_argument("--fault", default="none")
args = parser.parse_args()
cell = tiny_answer_long_cell()

import jax  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402

if args.fault == "rotary_half_zeroed":
    # what a chunk leaves in the cache has lost its rotary key: the chunks
    # after it, and every decode step, attend without k_r
    inner = decoder.mla_prefill

    def no_rotary_key(cfg, p, u, latent, slot, pos, n):
        out, latent = inner(cfg, p, u, latent, slot, pos, n)
        return out, latent.at[slot, :, cfg.kv_rank:].set(0)

    decoder.mla_prefill = no_rotary_key

ns = argparse.Namespace(workload=CELL, seed=5, seconds=4.0, trace=0)
try:
    line = run.run_cell(cell, ns, jax.devices()[:1])
except BaseException as failure:  # as run.main does: no result line, another exit code
    import traceback

    traceback.print_exc()
    print(f"benchmark: FAILED -- {failure!r}", file=sys.stderr, flush=True)
    sys.stderr.flush()
    os._exit(1)
print(json.dumps(line), flush=True)
sys.stdout.flush()
os._exit(0)
