"""The long-context answer cell (DeepSeek-V2 behind /v2/answer): it loads
with every published key, its cost arithmetic gives the issue's numbers,
its readers read what the program's spans say, a tiny whole run of
``rag_answer_by_reference`` + ``open_loop_answers_by_reference`` on the CPU
is ``correct``, and the same run with the latent cache's rotary half
zeroed underneath, and the control, are not. And the data-only cell
``bge-base.serve-steady`` loads."""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny_answer_long import CELL, tiny_answer_long_cell  # noqa: E402

import costs_deepseek_v2 as cost  # noqa: E402
import loader  # noqa: E402
import reference_deepseek_v2  # noqa: E402


def published_arch() -> dict:
    return reference_deepseek_v2.arch_of(loader.Cell(loader.load(), CELL).config)


def test_cell_loads_with_every_published_key():
    cell = loader.Cell(loader.load(), CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
    differ = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cell.config["source"] == row["source_url"]
    assert {k: cell.config["published"][k] for k in differ} == {
        k: row["config"][k] for k in differ}
    assert cell.chips == 1 and cell.config["reference"] == "reference_deepseek_v2"
    assert cell.config["serving"] == {"slots": 8, "prefill_chunk": 512, "max_positions": 16384}
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "query_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_prefill_roofline", "mla_decode_roofline", "mla_answer_step_mfu"} <= names
    # Granite's arithmetic is not read here
    assert not names & {"prefill_roofline", "decode_roofline", "answer_step_mfu"}
    for metric in cell.per_layer:
        assert callable(cell.reader(metric["name"]))
    granite = loader.Cell(loader.load(), "granite-4.0-h-small.answer-steady")
    # a prompt's 24 documents are its whole topic: answer_gap is a note here
    assert set(cell.limits["limits"]) == set(granite.limits["limits"]) - {"answer_gap"}
    assert cell.config["retriever"] == granite.config["retriever"]
    assert cell.config["index"]["reserved_space"] == granite.config["index"]["reserved_space"]


def test_the_other_new_cell_is_data_only():
    cell = loader.Cell(loader.load(), "bge-base.serve-steady")
    small = loader.Cell(loader.load(), "bge-small.serve-steady")
    assert cell.config["hidden_size"] == 768 and cell.config["index"]["reserved_space"] == 2097152
    cell.traffic.pop("rate_per_s"), small.traffic.pop("rate_per_s")
    cell.traffic.pop("why"), small.traffic.pop("why")
    assert cell.traffic == small.traffic            # the mix differs in its rate alone
    assert [m["name"] for m in cell.per_layer] == [m["name"] for m in small.per_layer]
    assert set(cell.limits["limits"]) == set(small.limits["limits"])


def test_the_seed_chooses_the_words_and_not_the_work():
    """Every length comes from ``shape_seed``: two seeds give other words in
    the same shapes; a prompt is its topic's 24 documents, 4,200 to 7,500
    tokens, every id inside the held rows."""
    cell = loader.Cell(loader.load(), CELL)
    gen, t = cell.generator, cell.traffic
    made = []
    for seed in (5, 2**31 + 1303):
        ctx = types.SimpleNamespace(traffic=t, seed=seed, config=cell.config, seconds=51.0)
        gen.make_inputs(ctx)
        made.append(ctx)
    a, b = made
    assert a.docs != b.docs and a.questions != b.questions
    assert [len(d.split()) for d in a.docs] == [len(d.split()) for d in b.docs]
    assert [len(q.split()) for q in a.questions] == [len(q.split()) for q in b.questions]
    assert len(a.questions) == round(t["rate_per_s"] * 51.0) and (a.due == b.due).all()
    assert len(a.sample) == t["check_answers"] == 4
    k = t["k"]
    assert k == 24
    _, asked = gen._asked(a, len(a.questions), 31)
    rows = cell.config["held"]["vocab_rows"][1]
    tokens, unk = [], []
    for q, g in zip(a.questions, asked):
        ids = gen.prompt_ids(a, gen.prompt_of(q, a.docs[k * g:k * g + k]))
        assert ids.max() < rows
        tokens.append(len(ids))
        unk.append((ids == gen.UNK).mean())
    # 447 of the corpus' 20,000 words lie outside the held rows: 1% of a
    # prompt's ids where no topic word does, up to a quarter where some do
    assert 0.005 < min(unk) < 0.02 and max(unk) < 0.3 and sum(unk) / len(unk) < 0.05
    # a question's suffix, by which the tap knows its prompt, is mapped alike
    late = max(range(len(a.questions)), key=lambda i: unk[i])
    suffix = gen.base._tokenizer().ids(f"Question: {a.questions[late]}\nAnswer:")[1:-1]
    assert max(suffix) >= rows                   # the accepted code's ids leave the slice
    mapped = gen.in_slice(a, suffix)
    g = asked[late]
    ids = gen.prompt_ids(a, gen.prompt_of(a.questions[late], a.docs[k * g:k * g + k]))
    assert mapped.max() < rows and (ids[-len(mapped):] == mapped).all()
    tokens.sort()
    assert 4200 <= tokens[0] and tokens[-1] <= 7500
    assert 5200 <= tokens[len(tokens) // 2] <= 6000
    assert -(-tokens[0] // 512) >= 9 and -(-tokens[-1] // 512) <= 15


def test_costs_give_the_issues_arithmetic():
    a = published_arch()
    assert cost.mla_params(a) == 149_225_472
    assert cost.expert_params(a) == 23_592_960
    assert cost.dense_matrix_params(a, "moe") == 197_230_592
    assert cost.dense_matrix_params(a, "dense") == 337_969_152
    assert cost.held_matrix_params(a) == 5_163_909_120
    assert cost.held_matrix_params(a) + cost.vector_params(a) == \
        reference_deepseek_v2.param_count(a) == 5_163_975_680
    assert abs(cost.held_param_bytes(a) - 10.33e9) < 0.01e9
    assert cost.expected_held_selections(a) == 1.5
    # a (query, cached position) pair: 128 heads x (192 + 128) x 2 a layer
    assert cost.attention_flops_per_pair(a) == 5 * 81_920
    # a prefill position outside attention: 0.46 GFLOP a held expert layer
    layer = 2 * (cost.dense_matrix_params(a, "moe") + 1.5 * cost.expert_params(a))
    assert abs(layer - 0.465e9) < 0.005e9
    # at a mean context of 2,800 attention is a third of an expert layer
    attention = 2800 * 81_920
    assert 0.30 < attention / (attention + layer) < 0.36
    # a request of 5,600 prompt tokens is about 21 TFLOP of prefill (the issue's
    # reckoning: 21 with the dense layer's MLP; 18.6 by this count's attention)
    prompt = 5600 * cost.token_flops(a, 1.5, 2800)
    assert 17e12 < prompt < 22e12
    # padding goes through the dense matrices, not through the experts or attention
    full = cost.prefill_chunk_flops(a, 512, 512, 1.5, 512 * 2800)
    half = cost.prefill_chunk_flops(a, 512, 256, 1.5, 256 * 2800)
    assert abs(full - 512 * cost.token_flops(a, 1.5, 2800) - cost.head_flops(a)) < 1
    assert 512 * cost.dense_flops_per_token(a) < half < full
    # a cache row: 1,152 B a position a layer; a decode step at batch 1 over
    # 5,600 positions touching 6 experts: dense 2.25 GB + head 0.26 + experts 0.28 + rows 0.03
    assert cost.latent_bytes_per_position(a) == 5 * 1152
    step = cost.decode_step_bytes(a, 6, 5600)
    assert abs(step - 2.83e9) < 0.05e9


class _Span(types.SimpleNamespace):
    pass


def test_readers_read_what_the_spans_say(monkeypatch):
    """The three ``mla_*`` readers over a made-up stretch: two prefill
    chunks and two decode steps whose spans say their contexts."""
    import mla_reduce
    import ring_reduce

    a = published_arch()
    spans = [
        _Span(name="answer.prefill", t0=10, args={"real": 512, "context": 1024}),
        _Span(name="answer.prefill", t0=20, args={"real": 100, "context": 1124}),
        _Span(name="answer.decode.step", t0=30, args={"batch": 2, "positions": 3000}),
        _Span(name="answer.decode.step", t0=40, args={"batch": 2, "positions": 3002}),
    ]
    st = ring_reduce.Stretch(spans, 0, 100)
    monkeypatch.setattr(ring_reduce, "stretch", lambda ctx: st)
    counters = {
        "expert_tokens": 0, "held_selections": 1500, "absent_selections": 4500,
        "prefill_real": 612, "prefill_padded": 412, "prompts": 1,
        "decode_experts_touched": 48, "decode_steps": {2: 4},
    }
    zero = {k: (0 if not isinstance(v, dict) else {}) for k, v in counters.items()}
    ctx = types.SimpleNamespace(
        darch=a, config={"serving": {"prefill_chunk": 512},
                         "trace_modules": {"prefill": "^jit_answer_prefill",
                                           "decode": "^jit_answer_decode", "search": "^jit_search"}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"interval": (0, 1), "window_s": 1.0,
               "module_runs": {"jit_answer_prefill": (2, 0.1), "jit_answer_decode": (2, 0.01)}},
        answer_tap=types.SimpleNamespace(counters_at={"open": zero, "close": counters}),
        arch={"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 12},
        tap=types.SimpleNamespace(batches=[]), capacity=1048576,
    )
    assert mla_reduce.held_selections_per_token(ctx) == 1.5
    first = 512 * 512 + 512 * 513 // 2
    second = 100 * 1024 + 100 * 101 // 2
    chunk = (cost.prefill_chunk_flops(a, 512, 512, 1.5, first)
             + cost.prefill_chunk_flops(a, 512, 100, 1.5, second)) / 2
    assert mla_reduce.mla_prefill_roofline(ctx) == pytest.approx(100 * 2 * chunk / 197e12 / 0.1)
    step = cost.decode_step_bytes(a, 12, 3001)
    assert mla_reduce.mla_decode_roofline(ctx) == pytest.approx(100 * 2 * step / 819e9 / 0.01)
    assert 0 < mla_reduce.mla_answer_step_mfu(ctx) < 100
    # a program whose spans lack the contexts: nothing to read, nothing raised
    for s in spans:
        s.args.pop("context", None), s.args.pop("positions", None)
    assert mla_reduce.mla_prefill_roofline(ctx) is None
    assert mla_reduce.mla_decode_roofline(ctx) is None
    assert mla_reduce.mla_answer_step_mfu(ctx) is None
    # and a run without a trace
    monkeypatch.setattr(ring_reduce, "stretch", lambda ctx: None)
    assert mla_reduce.mla_prefill_roofline(ctx) is None


def tiny_run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_answer_long_run.py"), "--fault", fault],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    line = tiny_run("none")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["state_gap"]["value"] > 0      # the latent rows were compared
    assert list(line)[-1] == "compared"


def test_a_cache_without_its_rotary_half_is_not_correct():
    line = tiny_run("rotary_half_zeroed")
    assert line["correct"] is False, line["compared"]
    assert line["failed"] == 0       # every reply came: only the comparison tells
    over = {k for k, row in line["compared"].items() if row["value"] > row["limit"]}
    assert {"logit_gap", "state_gap"} <= over


def test_control_is_not_correct():
    import control

    correct, compared = control.control_of(tiny_answer_long_cell(), 5, 4.0)
    assert correct is False, compared
    assert compared["missing_replies"]["value"] == 0
    over = {k for k, row in compared.items() if row["value"] > row["limit"]}
    assert over & {"logit_gap", "router_gap", "state_gap"}
