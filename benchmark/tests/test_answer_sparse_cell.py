"""The sparse-attention answer cell (GLM-5.2 behind /v2/answer): it loads
with every published key, its cost arithmetic gives the issue's numbers,
its readers read what the program's spans say, a tiny whole run of
``rag_answer_sparse`` + ``open_loop_answers_sparse`` on the CPU is
``correct``, and the same run with the timed path broken underneath (the
indexer keeps the wrong rows; a layer that shares a choice makes its own;
the index keys never reach the cache), and the control, are not."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny_answer_sparse import CELL, tiny_answer_sparse_cell  # noqa: E402

import costs_glm_dsa as cost  # noqa: E402
import loader  # noqa: E402
import reference_glm_dsa  # noqa: E402


def published_arch() -> dict:
    return reference_glm_dsa.arch_of(loader.Cell(loader.load(), CELL).config)


def test_cell_loads_with_every_published_key():
    cell = loader.Cell(loader.load(), CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    differ = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "indexer_types", "mlp_layer_types",
        "n_routed_experts", "vocab_size"}
    assert cell.config["source"] == row["source_url"]
    assert {k: cell.config["published"][k] for k in differ} == {
        k: row["config"][k] for k in differ}
    # the held lists are the published lists' entries 2-7
    assert cell.config["indexer_types"] == row["config"]["indexer_types"][2:8] == [
        "full", "shared", "shared", "shared", "full", "shared"]
    assert cell.config["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:8]
    assert cell.chips == 1 and cell.config["reference"] == "reference_glm_dsa"
    assert cell.config["serving"] == {
        "slots": 8, "prefill_chunk": 512, "max_positions": 20480, "first_layer": 2}
    assert cell.config["held"]["layers"] == [2, 6]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "query_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"dsa_prefill_roofline", "dsa_decode_roofline", "dsa_answer_step_mfu",
            "dsa_selected_share", "device_idle_share.answer", "gateway_wait_p50_ms"} <= names
    # the other answer models' arithmetic is not read here
    assert not names & {"prefill_roofline", "mla_prefill_roofline", "mla_answer_step_mfu"}
    for metric in cell.per_layer:
        assert callable(cell.reader(metric["name"]))
    long = loader.Cell(loader.load(), "DeepSeek-V2.answer-long")
    assert set(cell.limits["limits"]) == set(long.limits["limits"]) | {
        "index_gap", "wrong_selections"}
    assert cell.config["retriever"] == long.config["retriever"]
    assert cell.config["index"]["reserved_space"] == long.config["index"]["reserved_space"]
    # the mix is answer-long's but for the topic's size, the rate and what the check costs
    same = set(cell.traffic) - {"why", "rate_per_s", "k", "check_answers", "warm_answers",
                                "generator"}
    assert {k: cell.traffic[k] for k in same} == {k: long.traffic[k] for k in same}
    assert cell.traffic["k"] == 64 and cell.traffic["generator"] == "open_loop_answers_sparse"


def test_the_seed_chooses_the_words_and_not_the_work():
    """Every length comes from ``shape_seed``: a prompt is its topic's 64
    documents, 13,000 to 17,500 tokens, every id inside the held rows, with
    room for 32 new tokens in the cache. The words are those the held rows
    have a piece for: no quarter of a prompt is the one token ``[UNK]``,
    whose experts would be held or not as the seed's weights fall."""
    cell = loader.Cell(loader.load(), CELL)
    gen, t = cell.generator, cell.traffic
    made = []
    for seed in (5, 2**31 + 1303):
        ctx = types.SimpleNamespace(traffic=t, seed=seed, config=cell.config, seconds=51.0)
        gen.make_inputs(ctx)
        made.append(ctx)
    a, b = made
    assert a.docs != b.docs and a.questions != b.questions
    assert len(gen.base.corpus.words()) == 14635
    assert [len(d.split()) for d in a.docs] == [len(d.split()) for d in b.docs]
    assert len(a.questions) == round(t["rate_per_s"] * 51.0) and (a.due == b.due).all()
    k = t["k"]
    topics = [sum(len(d.split()) for d in a.docs[g * k:(g + 1) * k]) for g in range(64)]
    assert (min(topics), max(topics)) == (12560, 16336)
    _, asked = gen._asked(a, len(a.questions), 31)
    rows = cell.config["held"]["vocab_rows"][1]
    tokens = []
    for q, g in zip(a.questions, asked):
        ids = gen.prompt_ids(a, gen.prompt_of(q, a.docs[k * g:k * g + k]))
        assert ids.max() < rows
        assert (ids == gen.UNK).mean() < 0.001       # the template's pieces alone
        assert np.bincount(ids).max() <= 0.08 * len(ids)     # a topic's eight words, 1/16 each
        tokens.append(len(ids))
    room = cell.config["serving"]["max_positions"] - t["new_tokens"]
    assert 12500 <= min(tokens) and max(tokens) <= 18500 < room
    assert all(-(-n // 512) >= 25 for n in tokens)       # far past the 4 chunks index_topk covers


def test_costs_give_the_issues_arithmetic():
    a = published_arch()
    assert cost.mla_params(a) == 165_019_648
    assert cost.indexer_params(a) == 9_371_648
    assert cost.expert_params(a) == 37_748_736
    assert cost.dense_matrix_params(a, "dense", "full") == 400_883_712
    assert cost.dense_matrix_params(a, "moe", "shared") + 16 * cost.expert_params(a) == 808_321_024
    assert cost.dense_matrix_params(a, "moe", "full") + 16 * cost.expert_params(a) == 817_692_672
    assert cost.held_matrix_params(a) == 4_689_756_160
    assert cost.held_matrix_params(a) + cost.vector_params(a) == \
        reference_glm_dsa.param_count(a) == 4_689_853_184
    assert cost.held_param_bytes(a) == 9_379_900_416
    assert cost.expected_held_selections(a) == 0.5
    # a selected pair: 64 heads x (192 + 64 + 256) x 2 a layer, 6 layers; absorbed: 64 x (576 + 512) x 2
    assert cost.attention_flops_per_pair(a) == 6 * 65_536
    assert cost.absorbed_flops_per_pair(a) == 6 * 2 * 64 * (576 + 512)
    # a scored pair: 32 heads x 128 x 2 on each of the two layers that own an indexer
    assert cost.index_flops_per_pair(a) == 2 * 8_192
    assert cost.latent_bytes_per_position(a) == 6 * 1152
    assert cost.index_bytes_per_position(a) == 2 * 256
    # 3.05 GFLOP a token through the held matrices at the expected half selection
    assert abs(cost.token_flops(a, 0.5) - 3.05e9) < 0.01e9
    # a 15,100-token prompt keeps about 0.4 of the pairs plain MLA would attend
    n, keep = 15_100, 2048
    seen = n * (n + 1) // 2
    kept = sum(min(t + 1, keep) for t in range(n))
    assert 0.22 < kept / seen < 0.26      # of ALL visible pairs (the issue's 0.4 counts a mean context)
    # padding goes through the dense matrices, not the experts, the indexer's scores or attention
    full = cost.prefill_chunk_flops(a, 512, 512, 0.5, 512 * 7000, 512 * 2048)
    half = cost.prefill_chunk_flops(a, 512, 256, 0.5, 256 * 7000, 256 * 2048)
    assert 512 * cost.dense_flops_per_token(a) < half < full
    assert full == pytest.approx(
        512 * cost.token_flops(a, 0.5) + 512 * 7000 * 16_384 + 512 * 2048 * 393_216
        + cost.head_flops(a))
    # a decode step at batch 1 over 15,000 positions: the dense matrices 2.86 GB, the head
    # 0.24, half an expert a layer 0.19, the index keys 7.7 MB, the selected rows 14 MB
    step = cost.decode_step_bytes(a, 2.5, 15_000, 2048)
    assert abs(step - 3.31e9) < 0.02e9
    assert cost.decode_step_bytes(a, 2.5, 15_000, 15_000) - step == (15_000 - 2048) * 6912


class _Span(types.SimpleNamespace):
    pass


def test_readers_read_what_the_spans_say(monkeypatch):
    """The four ``dsa_*`` readers over a made-up stretch: two prefill
    chunks and two decode steps whose spans say what was scored and kept."""
    import dsa_reduce
    import ring_reduce

    a = published_arch()
    spans = [
        _Span(name="answer.prefill", t0=10,
              args={"real": 512, "context": 4096, "scored": 1966336, "selected": 1048576}),
        _Span(name="answer.prefill", t0=20,
              args={"real": 100, "context": 4196, "scored": 414650, "selected": 204800}),
        _Span(name="answer.decode.step", t0=30,
              args={"batch": 2, "positions": 9000, "selected": 4096}),
        _Span(name="answer.decode.step", t0=40,
              args={"batch": 2, "positions": 9002, "selected": 4096}),
    ]
    st = ring_reduce.Stretch(spans, 0, 100)
    monkeypatch.setattr(ring_reduce, "stretch", lambda ctx: st)
    counters = {
        "expert_tokens": 0, "held_selections": 500, "absent_selections": 7500,
        "prefill_real": 612, "prefill_padded": 412, "prompts": 1,
        "decode_experts_touched": 20, "decode_steps": {2: 4},
        "indexed_positions_prefill": 2380986, "selected_positions_prefill": 1253376,
        "indexed_positions_decode": 36004, "selected_positions_decode": 16384,
    }
    zero = {k: (0 if not isinstance(v, dict) else {}) for k, v in counters.items()}
    ctx = types.SimpleNamespace(
        darch=a, config={"serving": {"prefill_chunk": 512},
                         "trace_modules": {"prefill": "^jit_answer_prefill",
                                           "decode": "^jit_answer_decode", "search": "^jit_search"}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"interval": (0, 1), "window_s": 1.0,
               "module_runs": {"jit_answer_prefill": (2, 0.2), "jit_answer_decode": (2, 0.02)}},
        answer_tap=types.SimpleNamespace(counters_at={"open": zero, "close": counters}),
        arch={"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 12},
        tap=types.SimpleNamespace(batches=[]), capacity=1048576,
    )
    chunk = (cost.prefill_chunk_flops(a, 512, 512, 0.5, 1966336, 1048576)
             + cost.prefill_chunk_flops(a, 512, 100, 0.5, 414650, 204800)) / 2
    assert dsa_reduce.dsa_prefill_roofline(ctx) == pytest.approx(100 * 2 * chunk / 197e12 / 0.2)
    step = cost.decode_step_bytes(a, 5, 9001, 4096)
    assert dsa_reduce.dsa_decode_roofline(ctx) == pytest.approx(100 * 2 * step / 819e9 / 0.02)
    assert 0 < dsa_reduce.dsa_answer_step_mfu(ctx) < 100
    assert dsa_reduce.dsa_selected_share(ctx) == pytest.approx(
        100 * (1253376 + 16384) / (2380986 + 36004))
    # a program whose spans lack what was scored and kept (the parent's): nothing to read
    for s in spans:
        s.args.pop("scored", None), s.args.pop("selected", None)
    assert dsa_reduce.dsa_prefill_roofline(ctx) is None
    assert dsa_reduce.dsa_decode_roofline(ctx) is None
    assert dsa_reduce.dsa_answer_step_mfu(ctx) is None
    # and a program whose counters lack them
    for at in ctx.answer_tap.counters_at.values():
        for name in [k for k in at if k.startswith(("indexed_", "selected_"))]:
            del at[name]
    assert dsa_reduce.dsa_selected_share(ctx) is None
    # and a run without a trace
    monkeypatch.setattr(ring_reduce, "stretch", lambda ctx: None)
    assert dsa_reduce.dsa_prefill_roofline(ctx) is None


def tiny_run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_answer_sparse_run.py"), "--fault", fault],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    line = tiny_run("none")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    assert compared["state_gap"]["value"] > 0       # the latent rows and index keys were compared
    assert compared["index_gap"]["value"] > 0       # and the indexers' scores
    assert compared["wrong_selections"]["value"] == 0
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("fault", ["wrong_rows", "shared_layer_chooses", "index_keys_not_written"])
def test_a_broken_choice_is_not_correct(fault):
    line = tiny_run(fault)
    assert line["correct"] is False, line["compared"]
    assert line["failed"] == 0       # every reply came: only the comparison tells
    over = {k for k, row in line["compared"].items() if row["value"] > row["limit"]}
    assert {"logit_gap", "state_gap", "wrong_selections"} <= over


def test_control_is_not_correct():
    import control

    correct, compared = control.control_of(tiny_answer_sparse_cell(), 5, 4.0)
    assert correct is False, compared
    assert compared["missing_replies"]["value"] == 0
    over = {k for k, row in compared.items() if row["value"] > row["limit"]}
    assert over & {"logit_gap", "router_gap", "state_gap"}
    # the control's own choice of rows and index scores are judged as a served one's
    assert {"index_gap", "wrong_selections"} <= over
