"""A cell of BENCHMARK.json shrunk to what a CPU test holds: the same
files, generators, limits and checks, toy sizes. For tests only: the
command line of run.py has no such mode."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)

import loader  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

# made up for these tests: no device has these peaks, and no number
# computed from them is a device number
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
             "source": "made up for benchmark/tests"}


def tiny_cell(name: str):
    peaks.PEAKS["cpu"] = CPU_PEAKS
    reference.FILL_BLOCK_ROWS = 256
    cell = loader.Cell(loader.load(), name)
    cell.config.update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, max_position_embeddings=64, encoder_batch_size=32,
    )
    cell.config["index"].update(reserved_space=32768, fill_rows=2048)
    t = cell.traffic
    t["doc_words"]["cap"] = 60
    t["trace_seconds"] = 1.0
    if "setup_docs" in t:
        t.update(setup_docs=128, setup_commit_docs=64, rate_per_s=20.0, warm_rows=4,
                 warm_questions=8, check_questions=16, clients=8)
    else:
        t.update(commit_docs=64, pool_docs=30720, warm_commits=2, check_docs=16)
    return cell
