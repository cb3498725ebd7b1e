"""One tiny run of the answer cell on the CPU with the timed path broken
underneath (or not): prints the result line. Started by
test_answer_cell.py, one process a run."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_answer import CELL, tiny_answer_cell  # noqa: E402
from tiny import run  # noqa: E402

parser = argparse.ArgumentParser()
parser.add_argument("--fault", default="none")
args = parser.parse_args()
cell = tiny_answer_cell()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402

if args.fault == "state_not_carried":
    # every prefill chunk starts from an empty slot
    inner_chunk = decoder.prefill_chunk

    def forgetful(cfg, params, state, slot, ids, pos, n):
        return inner_chunk(cfg, params, jax.tree_util.tree_map(jnp.zeros_like, state),
                           slot, ids, pos, n)

    decoder.prefill_chunk = forgetful
elif args.fault == "expert_dropped":
    # the last held expert computes nothing
    inner_routed = decoder.routed_experts

    def dropped(cfg, p, u, sel, gates, held):
        last = cfg.experts_held[0] + cfg.experts_held[1] - 1
        return inner_routed(cfg, p, u, sel, gates, held & (sel != last))

    decoder.routed_experts = dropped
elif args.fault == "fp8_operands":
    # every matrix product's operands rounded to float8_e4m3fn
    def fp8(x):
        x = x.astype(jnp.float32)
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return ((x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
                ).astype(jnp.bfloat16)

    def mm(a, w):
        return jnp.dot(fp8(a), fp8(w), preferred_element_type=jnp.float32)

    decoder._mm = mm

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
