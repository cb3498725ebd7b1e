"""One tiny run of the sparse-attention answer cell on the CPU with the
timed path broken underneath (or not): prints the result line. Started by
test_answer_sparse_cell.py, one process a run."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_answer_sparse import CELL, tiny_answer_sparse_cell  # noqa: E402
from tiny import run  # noqa: E402

parser = argparse.ArgumentParser()
parser.add_argument("--fault", default="none")
args = parser.parse_args()
cell = tiny_answer_sparse_cell()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402

if args.fault == "wrong_rows":
    # the indexer keeps the WORST rows of every query: the scores' sign is lost
    inner = decoder.select_rows
    decoder.select_rows = lambda scores, visible, k: inner(-scores, visible, k)
elif args.fault == "shared_layer_chooses":
    # a layer that shares a choice makes one of its own instead (from its
    # latent rows' first values: it has no indexer): what travels is lost
    inner = decoder.mla_attend

    def own_choice(cfg, q_nope, q_rope, latent, w_ukv, slot, pos, chosen=None):
        if chosen is not None and own_choice.layer % 5 in (1, 2, 4):
            T, P = chosen.shape
            scores = jnp.broadcast_to(latent[slot, :, 0].astype(jnp.float32), (T, P))
            visible = jnp.arange(P)[None, :] <= (pos + jnp.arange(T))[:, None]
            chosen = decoder.select_rows(scores, visible, cfg.index_topk).astype(jnp.int32)
        own_choice.layer += 1
        return inner(cfg, q_nope, q_rope, latent, w_ukv, slot, pos, chosen)

    own_choice.layer = 0
    decoder.mla_attend = own_choice
elif args.fault == "index_keys_not_written":
    # a chunk's index keys never reach the cache: later chunks and every
    # decode step score zeros
    inner = decoder.index_prefill

    def unwritten(cfg, p, u, c_q, keys, slot, pos, n):
        chosen, scores, _ = inner(cfg, p, u, c_q, keys, slot, pos, n)
        return chosen, scores, keys

    decoder.index_prefill = unwritten

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
