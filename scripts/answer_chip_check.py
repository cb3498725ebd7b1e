#!/usr/bin/env python3
"""An answer model at its published widths on the chip, outside the
server: one chip's share of an answer cell's configuration (``--workload``:
granite-4.0-h-small.answer-steady, DeepSeek-V2.answer-long,
GLM-5.2.answer-sparse) made from a seed, a few prompts prefilled in chunks
and decoded through the cache, held to the configuration's plain
reference's one full forward; times a request alone; prints the device's
memory peak. Under an indexer the reference follows the served choice of
rows on every query inside the cell's index tolerance (a kept generation
carries them as bits; the fp8 control's own choice is judged the same way), and ``index_gap`` / ``wrong_selections`` say how
far the two indexers lie apart (the scores: on the decode steps);
``--alone 1`` also runs it following the choice of those last queries
only (``kept``) and choosing for itself everywhere
(``alone``): what near-ties between the two indexers cost each number.

    chiprun -- python scripts/answer_chip_check.py [--workload CELL] [--seed N] \
        [--lengths 700,1400,3100] [--control 1] [--alone 1]

Refuses any backend but ``tpu``."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import numpy as np  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="granite-4.0-h-small.answer-steady")
    parser.add_argument("--seed", type=int, default=2147484001)
    parser.add_argument("--lengths", default="700,1400,3100")
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--alone", type=int, default=0)
    args = parser.parse_args()
    import jax

    if jax.default_backend() != "tpu":
        print("answer_chip_check: no TPU -- refusing", file=sys.stderr)
        return 2
    import importlib

    import loader
    from pathway_tpu.models import decoder as dec

    cell = loader.Cell(loader.load(), args.workload)
    config = cell.config
    ref = importlib.import_module(config.get("reference", "reference_decoder"))
    a = ref.arch_of(config)
    cfg = dec.DecoderConfig.from_hf(
        {**config, **{k: config["published"][k] for k in config["reduced"]}},
        layers=config["num_hidden_layers"], experts_held=tuple(config["held"]["experts"]),
        vocab_held=tuple(config["held"]["vocab_rows"]), **config["serving"])
    t0 = time.monotonic()

    class Ctx:
        darch, seed, config = a, args.seed, cell.config

    params = cell.pipeline.decoder_params(Ctx)
    jax.block_until_ready(params)
    model = dec.AnswerModel(cfg, params)
    print(json.dumps({"weights_s": round(time.monotonic() - t0, 2),
                      "param_bytes": dec.param_bytes(cfg), "cache_bytes": dec.cache_bytes(cfg)}),
          flush=True)
    rng = np.random.default_rng(args.seed & 0xFFFFFFFF)
    lengths = [int(n) for n in args.lengths.split(",")]
    prompts = [rng.integers(1000, min(26000, a["vocab_rows"]), size=n).astype(np.int32)
               for n in lengths]
    new = 32
    sparse = bool(getattr(cfg, "index_topk", 0))
    for label in ("cold", "warm", "warm2"):
        t = time.monotonic()
        made = model.generate(prompts, new, keep=range(len(prompts)))
        print(json.dumps({"generate": label, "seconds": round(time.monotonic() - t, 3)}), flush=True)
    # one prompt alone, timed: its chunks and its 31 steps
    for n in lengths:
        t = time.monotonic()
        model.generate([prompts[lengths.index(n)]], new)
        print(json.dumps({"alone_tokens": n, "seconds": round(time.monotonic() - t, 4)}), flush=True)
    t = time.monotonic()
    model.generate([prompts[0][:500]] * 8, new)
    print(json.dumps({"eight_of_500": round(time.monotonic() - t, 4)}), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"memory_peak_bytes": stats.get("peak_bytes_in_use"),
                      "bytes_in_use": stats.get("bytes_in_use")}), flush=True)
    # the state the reference gives back: final SSM states, or the
    # sequence's own latent cache rows (and index keys)
    states = [[np.asarray(layer) for layer in g.ssm] if g.latent is None else
              [np.asarray(rows[:len(g.prompt) + new - 1], np.float32) for rows in g.latent]
              for g in made]
    tol = cell.limits.get("tolerances", {})
    following = {}
    if sparse:      # the served indexers' work, for the reference to follow
        index_tol = float(tol["index"])
        choices = [g.choices() for g in made]
        following = {"index_tol": index_tol, "selections": [
            {"at": np.arange(len(g.prompt) + new - 1), "chosen": chosen, "scores": scores}
            for g, (chosen, scores) in zip(made, choices)]}
        last = [len(g.prompt) + np.arange(new - 1) for g in made]
        kept = {"index_tol": index_tol, "selections": [
            {"at": at, "chosen": chosen[:, at], "scores": scores}
            for at, (chosen, scores) in zip(last, choices)]}
    for leaf in jax.tree_util.tree_leaves((model.params, model.cache.state)):
        leaf.delete()
    seqs = [np.concatenate([g.prompt, g.tokens[:-1]]) for g in made]
    routes = [np.concatenate([g.prompt_routes, g.decode_routes], axis=1) for g in made]
    router_tol = float(tol.get("router", 0.05))
    runs = ("f32",) + (("kept", "alone") if sparse and args.alone else ()) + (
        ("fp8",) if args.control else ())
    for precision in runs:
        t = time.monotonic()
        if precision == "fp8":
            out = ref.forward(a, args.seed, seqs, last=new, router_tol=router_tol, precision="fp8",
                              **({"keep_chosen": True} if sparse else {}))
        else:       # "alone": the reference chooses its rows for itself on every query
            follow = {"f32": following, "kept": kept, "alone": {}}[precision]
            out = ref.forward(a, args.seed, seqs, last=new, routes=routes, router_tol=router_tol,
                              **follow)
        secs = round(time.monotonic() - t, 2)
        if precision == "fp8":   # the control's own choice of rows is followed and judged too
            own = {"index_tol": index_tol, "selections": [
                {"at": np.arange(len(s)), "chosen": o["chosen"], "scores": o["index_scores"]}
                for s, o in zip(seqs, out)]} if sparse else {}
            base = ref.forward(a, args.seed, seqs, last=new, routes=[o["routes"] for o in out],
                               router_tol=router_tol, **own)
        for i, (g, o) in enumerate(zip(made, out)):
            want = (base[i] if precision == "fp8" else o)["logits"].astype(np.float64)
            served = precision != "fp8"
            got = g.logits if served else o["logits"]
            ref_states = (o if served else base[i])["states"]
            got_states = states[i] if served else o["states"]
            spread = want.max(-1) - want.min(-1)
            gap = (np.abs(got - want).max(-1) / spread).max()
            tok = g.tokens if served else got.argmax(-1)
            behind = ((want.max(-1) - want[np.arange(new), tok]) / spread).max()
            state_gap = max(float(np.abs(x - y).max() / np.abs(y).max())
                            for x, y in zip(got_states, ref_states))
            rgap = (o if served else base[i])
            index = {k: rgap[k] for k in ("index_gap", "wrong_selections")
                     if k in rgap and precision != "alone"}
            print(json.dumps({
                "precision": precision, "tokens": len(seqs[i]), "reference_s": secs,
                "logit_gap": float(gap), "token_gap": float(behind), "state_gap": state_gap,
                "router_gap": rgap["router_gap"], "wrong_routes": rgap["wrong_routes"], **index,
                "logit_spread": float(spread.mean()),
                "agree": int((tok == want.argmax(-1)).sum()),
            }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
