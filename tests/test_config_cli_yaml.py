"""Config / CLI / YAML-template / monitoring tests."""

import io
import json
import os
import subprocess
import sys

import pytest
import threading
import time
import urllib.request

import pathway_tpu as pw


def test_pathway_config_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_THREADS", "4")
    monkeypatch.setenv("PATHWAY_PROCESS_ID", "2")
    monkeypatch.setenv("PATHWAY_IGNORE_ASSERTS", "true")
    cfg = pw.PathwayConfig()
    assert cfg.threads == 4
    assert cfg.process_id == 2
    assert cfg.ignore_asserts is True


def test_yaml_loader_instantiates_objects():
    template = """
$dimension: 12
embedder: !pw.xpacks.llm.mocks.DeterministicMockEmbedder
  dimension: $dimension
splitter: !pw.xpacks.llm.splitters.TokenCountSplitter
  min_tokens: 5
  max_tokens: 100
name: demo
"""
    out = pw.load_yaml(io.StringIO(template))
    from pathway_tpu.xpacks.llm.mocks import DeterministicMockEmbedder
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

    assert isinstance(out["embedder"], DeterministicMockEmbedder)
    assert out["embedder"].dimension == 12
    assert isinstance(out["splitter"], TokenCountSplitter)
    assert out["splitter"].max_tokens == 100
    assert out["name"] == "demo"


def test_cli_spawn_runs_program(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import os\n"
        "print('pid', os.environ['PATHWAY_PROCESS_ID'])\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "spawn", str(prog)],
        capture_output=True,
        timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.getcwd(),
    )
    assert proc.returncode == 0
    assert b"pid 0" in proc.stdout


def test_cli_spawn_fails_with_the_first_failed_rank(tmp_path, capsys):
    """A rank that dies at start-up (on a chip host: the rank that lost
    the race for the device) must fail the launch at once, not after its
    peers waited out the mesh connect timeout."""
    import time

    from pathway_tpu import cli

    prog = tmp_path / "prog.py"
    prog.write_text(
        "import os, sys, time\n"
        "if os.environ['PATHWAY_PROCESS_ID'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(120)\n"
    )
    t0 = time.monotonic()
    assert cli.main(["spawn", "-n", "2", str(prog)]) == 3
    assert time.monotonic() - t0 < 30
    assert "rank 1 exited with code 3" in capsys.readouterr().err


def test_metrics_http_server(monkeypatch):
    import os

    if os.environ.get("PATHWAY_LANE_PROCESSES"):
        # reference pattern skip_on_multiple_workers (tests/utils.py:48):
        # this test reassigns PATHWAY_PROCESS_ID and reloads the config
        # module, which cannot compose with the emulated-rank overlay
        pytest.skip("incompatible with the emulated-rank lane")
    monkeypatch.setenv("PATHWAY_PROCESS_ID", "931")
    import importlib

    import pathway_tpu.internals.config as cfg_mod

    importlib.reload(cfg_mod)

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(3):
                self.next(v=i)
                self.commit()
            time.sleep(2.0)

    class S(pw.Schema):
        v: int

    t = pw.io.python.read(Subj(), schema=S, autocommit_duration_ms=None, name="gen")
    pw.io.subscribe(t, on_change=lambda *a: None)

    def run():
        from pathway_tpu.internals.graph_runner import GraphRunner

        GraphRunner(with_http_server=True).run_outputs()

    threading.Thread(target=run, daemon=True).start()
    time.sleep(1.0)
    with urllib.request.urlopen("http://127.0.0.1:20931/metrics", timeout=5) as r:
        body = r.read().decode()
    assert "connector_rows_total" in body
    assert 'connector="gen"' in body
    assert "output_rows_total" in body
