"""Bring-up guards (ISSUE 21): the pieces that keep a chip run honest and
cheap — chip_smoke.py refuses the CPU, the compile cache has one fixed
home that the environment can move, asking for more index shards than
devices is an error, and native binaries are rebuilt on a content change
and only then. CPU-only, ~5 s in total (three short subprocesses)."""

import json
import logging
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(args, *, env_delta, cwd=REPO, timeout=120):
    env = dict(os.environ)
    for key, value in env_delta.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, *args], env=env, cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
    )


def test_chip_smoke_refuses_any_backend_but_tpu():
    proc = _python(["chip_smoke.py"], env_delta={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "refusing" in proc.stderr
    # no result line: nothing on stdout parses as the {"ok": ...} object
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


_DEVICE_PY = os.path.join(REPO, "pathway_tpu", "internals", "device.py")
# device.py loaded by path: the same code without the 3 s package import
_PLACE = (
    "import importlib.util, json, sys, jax\n"
    f"spec = importlib.util.spec_from_file_location('device', {_DEVICE_PY!r})\n"
    "device = sys.modules['device'] = importlib.util.module_from_spec(spec)\n"
    "spec.loader.exec_module(device)\n"
    "print(json.dumps([device.place_compile_cache(),"
    " jax.config.jax_compilation_cache_dir]))\n"
)


def test_compile_cache_has_one_fixed_home_under_the_checkout(tmp_path):
    """No JAX_COMPILATION_CACHE_DIR: <checkout>/.jax_cache, derived from
    the package's own path — the same from any working directory (the
    directory is part of the cache key; a moving one never hits)."""
    want = os.path.join(REPO, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    for cwd in (tmp_path, other):
        proc = _python(
            ["-c", _PLACE], cwd=str(cwd),
            env_delta={"JAX_COMPILATION_CACHE_DIR": None, "JAX_PLATFORMS": None},
        )
        assert proc.returncode == 0, proc.stderr
        assert json.loads(proc.stdout.splitlines()[-1]) == [want, want]


def test_compile_cache_env_wins_and_cpu_gets_none(tmp_path, monkeypatch):
    import jax

    from pathway_tpu.internals.device import place_compile_cache

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.append(key)
    )
    # placed from outside: JAX reads the variable itself, code sets nothing
    placed = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert place_compile_cache() == placed and updates == []
    # this process is pinned to the CPU (conftest): no cache at all
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert jax.config.jax_platforms == "cpu"
    assert place_compile_cache() == "" and updates == []
    assert jax.config.jax_compilation_cache_dir is None


def test_auto_mesh_raises_when_shards_exceed_devices(monkeypatch):
    import jax

    from pathway_tpu.stdlib.indexing.nearest_neighbors import _auto_mesh

    n = len(jax.devices())
    monkeypatch.setenv("PATHWAY_INDEX_SHARDS", str(n + 1))
    with pytest.raises(RuntimeError, match=f"only {n} cpu device"):
        _auto_mesh()
    monkeypatch.setenv("PATHWAY_INDEX_SHARDS", str(n))
    assert _auto_mesh().shape["dp"] == n


def test_native_stamp_follows_content_not_mtime(tmp_path, monkeypatch, caplog):
    from pathway_tpu import native

    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_PREBUILT_DIR", None)
    builds = []
    real_run = subprocess.run

    def counting_run(cmd, **kw):
        builds.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native.subprocess, "run", counting_run)
    src = tmp_path / "probe.c"
    src.write_text("int probe(void) { return 1; }\n")
    cc = ["gcc", "-shared", "-fPIC"]

    def build(compiler=cc):
        return native._compile("probe", "probe.so", compiler, [str(src)])

    out = build()
    assert out and os.path.exists(out) and len(builds) == 1
    stamp = native.loaded_fingerprints()["probe"]
    # a touch — sources newer than the binary, or the reverse — is not a
    # change: a copied tree gets fresh mtimes and the same contents
    os.utime(src, (2_000_000_000, 2_000_000_000))
    assert build() == out and len(builds) == 1
    os.utime(src, (1_000_000_000, 1_000_000_000))
    assert build() == out and len(builds) == 1
    # a content change rebuilds, whatever the mtimes say
    src.write_text("int probe(void) { return 2; }\n")
    os.utime(src, (1_000_000_000, 1_000_000_000))
    assert build() == out and len(builds) == 2
    assert native.loaded_fingerprints()["probe"] != stamp
    # so does a different compile command
    assert build([*cc, "-O1"]) == out and len(builds) == 3
    # a failed build falls back (None) and says why, with the compiler's
    # own words
    src.write_text("int probe(void) { return }\n")
    with caplog.at_level(logging.WARNING, logger=native.__name__):
        assert build() is None
    assert "probe.so" in caplog.text and "error" in caplog.text
