"""The comparison that decides ``correct`` has been shown to fail: the
control (the reference one precision down, in the program's place), and
a whole run with the timed path broken underneath, each come out as not
correct under the cells' own limits; the unbroken run comes out correct."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("bge-small.serve-steady", "bge-base.ingest-bulk")


def tiny_run(workload: str, fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tiny_run.py"), "--workload", workload,
         "--fault", fault],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if done.returncode != 0:
        # the run failed loudly and printed no result line: nothing reads as correct
        assert "benchmark" in done.stderr or "Error" in done.stderr, done.stderr[-3000:]
        assert fault != "none", done.stderr[-3000:]
        return {"correct": False, "compared": done.stderr[-500:]}
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = tiny_run(workload, "none")
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.mark.parametrize(
    "workload,fault",
    [
        ("bge-small.serve-steady", "answer_altered"),
        ("bge-small.serve-steady", "embedding_altered"),
        ("bge-small.serve-steady", "state_unchanged"),
        ("bge-base.ingest-bulk", "embedding_altered"),
        ("bge-base.ingest-bulk", "state_unchanged"),
    ],
)
def test_broken_path_is_not_correct(workload, fault):
    line = tiny_run(workload, fault)
    assert line["correct"] is False, (fault, line["compared"])


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    sys.path.insert(0, HERE)
    from tiny import tiny_cell

    import control

    correct, compared = control.control_of(tiny_cell(workload), 5, 3.0)
    assert correct is False, compared
