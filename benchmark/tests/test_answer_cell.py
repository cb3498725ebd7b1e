"""The answer cell: it loads, its cost arithmetic gives the issue's
numbers, a tiny whole run of ``rag_answer`` + ``open_loop_answers`` on the
CPU is ``correct``, and the same run with the timed path broken underneath
(state not carried between chunks, one expert dropped, fp8 operands), and
the control, are not."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny_answer import CELL, tiny_answer_cell  # noqa: E402

import costs_decoder  # noqa: E402
import loader  # noqa: E402
import reference_decoder  # noqa: E402


def published_arch() -> dict:
    return reference_decoder.arch_of(loader.Cell(loader.load(), CELL).config)


def test_cell_loads_with_every_published_key():
    cell = loader.Cell(loader.load(), CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    differ = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "num_local_experts", "vocab_size"}
    assert cell.config["source"] == row["source_url"]
    assert {k: cell.config["published"][k] for k in differ} == {
        k: row["config"][k] for k in differ}
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "query_p95_ms", "setup_s"}
    assert {m["moves"] for m in cell.per_layer} == {"query_p50_ms", "query_p95_ms"}
    for metric in cell.per_layer:
        assert callable(cell.reader(metric["name"]))
    for name in cell.limits["limits"]:
        assert name in (
            "wrong_answers", "missing_replies", "question_embed_gap", "doc_embed_gap",
            "answer_gap", "score_gap", "rank_gap", "scan_gap", "logit_gap", "token_gap",
            "wrong_tokens", "router_gap", "wrong_routes", "state_gap")


def test_the_seed_chooses_the_words_and_not_the_work():
    """Document and question lengths, and which topic a question asks
    about, come from the mix's ``shape_seed``: two seeds give other words
    in the same shapes, and a question's words are its topic's own."""
    import types

    cell = loader.Cell(loader.load(), CELL)
    gen, t = cell.generator, cell.traffic
    made = []
    for seed in (5, 2**31 + 1303):
        ctx = types.SimpleNamespace(traffic=t, seed=seed, config=cell.config, seconds=51.0)
        gen.make_inputs(ctx)
        made.append(ctx)
    a, b = made
    assert a.docs != b.docs and a.questions != b.questions
    for ctx in made:
        words = [len(d.split()) for d in ctx.docs]
        assert len(words) == t["setup_docs"] and min(words) == t["doc_words"]["scale"]
        assert max(words) == t["doc_words"]["cap"]
    assert [len(d.split()) for d in a.docs] == [len(d.split()) for d in b.docs]
    assert [len(q.split()) for q in a.questions] == [len(q.split()) for q in b.questions]
    assert len(a.questions) == round(t["rate_per_s"] * 51.0) and (a.due == b.due).all()
    k = t["k"]
    _, asked = gen._asked(a, len(a.questions), 31)
    for ctx in made:
        for q, g in zip(ctx.questions, asked):
            own = set(" ".join(ctx.docs[k * g:k * g + k]).split())
            assert set(q.split()[:-1]) <= own
    # a prompt of the k documents of one topic: 700 to 3,200 tokens, median near 1,400
    tokens = sorted(
        len(gen.prompt_ids(a, gen.prompt_of(q, a.docs[k * g:k * g + k])))
        for q, g in zip(a.questions, asked))
    assert 700 <= tokens[0] and tokens[-1] <= 3200
    assert 1300 <= tokens[len(tokens) // 2] <= 1550


def test_the_other_new_cell_loads():
    cell = loader.Cell(loader.load(), "bge-small.ingest-bulk")
    assert cell.config["hidden_size"] == 384 and cell.traffic["commit_docs"] == 512
    assert {m["name"] for m in cell.end_to_end} == {"ingest_docs_per_s", "setup_s"}
    assert set(cell.limits["limits"]) == {"missing_docs", "doc_embed_gap"}


def test_costs_give_the_issues_arithmetic():
    a = published_arch()
    # 4,757 M parameters, 9.51 GB in bfloat16
    assert abs(costs_decoder.held_params(a) - 4757e6) < 1e6
    assert abs(costs_decoder.held_param_bytes(a) - 9.51e9) < 0.01e9
    assert costs_decoder.held_params(a) == reference_decoder.param_count(a)
    # a Mamba layer 102.29 M dense + router 0.29 + shared 18.87; an expert 9.437 M
    assert abs(costs_decoder.dense_matrix_params(a, "mamba") - (102.24e6 + 0.29e6 + 18.87e6)) < 0.1e6
    assert abs(costs_decoder.expert_params(a) - 9.437e6) < 1e3
    # 3.3 GFLOP a prompt token through the held share (5 of 10 selections held)
    assert costs_decoder.expected_held_selections(a) == 5.0
    assert abs(costs_decoder.flops_per_token(a) - 3.3e9) < 0.06e9
    # 3.7 GB a decode step at batch 1: dense 2.31 + 50 touched experts 0.94 + head 0.41
    step = costs_decoder.decode_step_bytes(a, 1, 50, 1400)
    assert abs(step - 3.7e9) < 0.1e9
    # a sequence's state: 9 x (4.19 MB + 50.7 kB); keys/values 4 kB a position
    assert abs(costs_decoder.state_bytes_per_sequence(a) - 9 * 4.245e6) < 1e4
    assert costs_decoder.kv_bytes_per_position(a) == 4096
    # padding goes through the dense matrices, not through the experts
    full = costs_decoder.prefill_chunk_flops(a, 512, 512, 5.0)
    half = costs_decoder.prefill_chunk_flops(a, 512, 256, 5.0)
    assert abs(full - 512 * costs_decoder.flops_per_token(a) - costs_decoder.head_flops(a)) < 1
    assert half < full and half > 512 * costs_decoder.dense_flops_per_token(a)


def tiny_run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_answer_run.py"), "--fault", fault],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    line = tiny_run("none")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("fault", ["state_not_carried", "expert_dropped", "fp8_operands"])
def test_broken_path_is_not_correct(fault):
    line = tiny_run(fault)
    assert line["correct"] is False, (fault, line["compared"])
    assert line["failed"] == 0       # every reply came: only the comparison tells


def test_control_is_not_correct():
    import control

    correct, compared = control.control_of(tiny_answer_cell(), 5, 4.0)
    assert correct is False, compared
    assert compared["missing_replies"]["value"] == 0
