"""Native C++ runtime components — build + ctypes bindings.

The reference's native core is Rust (tantivy BM25, usearch HNSW,
brute-force ndarray KNN — src/external_integration/). Here the host-side
index runtimes are C++ (native/bm25.cpp, native/hnsw.cpp) compiled once
into a shared library and bound via ctypes; the dense brute-force path
stays on TPU (pathway_tpu.ops). Pure-Python fallbacks keep everything
working when no toolchain is present.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig
import threading
from typing import Any, Sequence

import numpy as np

_LOG = logging.getLogger(__name__)

_REPO_NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
# override point for instrumented builds (scripts/sanitize_native.sh
# compiles the extensions with ASAN/TSAN into a scratch dir). Whoever
# set the override owns that directory: a binary found there is loaded
# as it is, never rebuilt over.
_PREBUILT_DIR = os.environ.get("PATHWAY_NATIVE_BUILD_DIR")
_BUILD_DIR = _PREBUILT_DIR or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_build"
)
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False
# name -> fingerprint of the sources + compile command each loaded
# binary was built from (chip_smoke.py prints these)
_FINGERPRINTS: dict[str, str] = {}


def _source_dir() -> str:
    if os.path.isdir(_REPO_NATIVE):
        return _REPO_NATIVE
    # installed layout: sources shipped next to this package
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


def _fingerprint(compiler: Sequence[str], files: Sequence[str]) -> str:
    """Hash of the compile command and the CONTENTS of every source and
    header it reads. Staleness is decided on this, not on mtimes: a tree
    copied with an old ``_build/`` beside fresh-mtimed sources (or the
    reverse) must not load a binary that does not match its source."""
    h = hashlib.sha256("\0".join(compiler).encode())
    for path in files:
        h.update(b"\0" + os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _compile(
    name: str,
    out_name: str,
    compiler: Sequence[str],
    sources: Sequence[str],
    headers: Sequence[str] = (),
    *,
    timeout: float = 180,
) -> str | None:
    """Path of the up-to-date binary for ``sources``, building it when
    the stamp beside it does not match :func:`_fingerprint`; None (with
    a logged warning carrying the compiler's stderr tail) when a source
    is missing or the build fails — callers fall back to pure Python."""
    if not all(os.path.exists(s) for s in sources):
        return None
    out = os.path.join(_BUILD_DIR, out_name)
    if _PREBUILT_DIR and os.path.exists(out):
        _FINGERPRINTS[name] = "prebuilt"
        return out
    headers = [h for h in headers if os.path.exists(h)]
    want = _fingerprint(compiler, [*sources, *headers])
    stamp = out + ".stamp"
    try:
        with open(stamp) as f:
            fresh = os.path.exists(out) and f.read().strip() == want
    except OSError:
        fresh = False
    if not fresh:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # build beside the target and rename into place: a concurrent
        # process (another rank, another test) never loads a half-
        # written binary
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                [*compiler, "-o", tmp, *sources],
                check=True, capture_output=True, timeout=timeout,
            )
            os.replace(tmp, out)
        except (subprocess.SubprocessError, OSError) as exc:
            # a failed build silently drops this library (callers run in
            # pure Python) — make the degradation visible. g++ 10 works
            # (exec.cpp gates its C++20 library uses); g++ < 10 rejects
            # -std=c++20
            stderr = getattr(exc, "stderr", None) or b""
            _LOG.warning(
                "native build of %s failed (%s): %s",
                out_name, exc, stderr[-500:],
            )
            if os.path.exists(tmp):
                os.unlink(tmp)
            return None
        with open(stamp, "w") as f:
            f.write(want)
    _FINGERPRINTS[name] = want
    return out


def loaded_fingerprints() -> dict[str, str]:
    """``{library: source+command fingerprint}`` for every native binary
    this process built or found fresh — what the loaded code was
    compiled from."""
    return dict(_FINGERPRINTS)


def _import_extension(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except ImportError as exc:
        _LOG.warning("native extension %s failed to import: %s", path, exc)
        _FINGERPRINTS.pop(name, None)
        return None
    return mod


def get_lib() -> ctypes.CDLL | None:
    """Compile-on-first-use; None when no toolchain (callers fall back)."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        src_dir = _source_dir()
        path = _compile(
            "libpathway_native", "libpathway_native.so",
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"],
            [os.path.join(src_dir, "bm25.cpp"),
             os.path.join(src_dir, "hnsw.cpp")],
        )
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.bm25_new.restype = ctypes.c_void_p
        lib.bm25_new.argtypes = [ctypes.c_double, ctypes.c_double]
        lib.bm25_free.argtypes = [ctypes.c_void_p]
        lib.bm25_add.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p]
        lib.bm25_remove.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.bm25_len.restype = ctypes.c_int64
        lib.bm25_len.argtypes = [ctypes.c_void_p]
        lib.bm25_search.restype = ctypes.c_int64
        lib.bm25_search.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
        ]
        lib.hnsw_new.restype = ctypes.c_void_p
        lib.hnsw_new.argtypes = [ctypes.c_int32] * 5
        lib.hnsw_free.argtypes = [ctypes.c_void_p]
        lib.hnsw_add.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_float)
        ]
        lib.hnsw_add_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64,
        ]
        lib.hnsw_remove.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hnsw_len.restype = ctypes.c_int64
        lib.hnsw_len.argtypes = [ctypes.c_void_p]
        lib.hnsw_search.restype = ctypes.c_int64
        lib.hnsw_search.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
        ]
        _LIB = lib
        return _LIB


def available() -> bool:
    return get_lib() is not None


def _python_extension(name: str, compiler: list[str], source: str,
                      timeout: float):
    """Build (if stale) and import one CPython extension from
    ``native/<source>``; shared headers count toward staleness."""
    src_dir = _source_dir()
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    include = sysconfig.get_paths()["include"]
    path = _compile(
        name, name + suffix,
        [*compiler, f"-I{include}"],
        [os.path.join(src_dir, source)],
        [os.path.join(src_dir, "pw_blake2b.h")],
        timeout=timeout,
    )
    return None if path is None else _import_extension(name, path)


_FASTPATH = None
_FASTPATH_TRIED = False


def get_fastpath():
    """CPython extension with the engine's per-row hot loops
    (native/fastpath.c); None when no toolchain — callers fall back to the
    pure-Python implementations."""
    global _FASTPATH, _FASTPATH_TRIED
    with _LOCK:
        if _FASTPATH_TRIED:
            return _FASTPATH
        _FASTPATH_TRIED = True
        _FASTPATH = _python_extension(
            "fastpath", ["gcc", "-O3", "-shared", "-fPIC"], "fastpath.c",
            timeout=120,
        )
        return _FASTPATH


_PWEXEC = None
_PWEXEC_TRIED = False


def get_pwexec():
    """CPython extension with the sharded native group-by executor
    (native/exec.cpp) — the multi-worker relational engine core. None when
    no toolchain; callers fall back to the Python operator path."""
    global _PWEXEC, _PWEXEC_TRIED
    with _LOCK:
        if _PWEXEC_TRIED:
            return _PWEXEC
        _PWEXEC_TRIED = True
        _PWEXEC = _python_extension(
            "pwexec",
            ["g++", "-O3", "-std=c++20", "-shared", "-fPIC", "-pthread"],
            "exec.cpp", timeout=180,
        )
        return _PWEXEC


class NativeBm25:
    """ctypes wrapper over the C++ BM25 index. int64 handles are minted
    per key by the caller (KeyToU64IdMapper pattern, reference
    external_integration/mod.rs)."""

    def __init__(self, k1: float = 1.2, b: float = 0.75):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.bm25_new(k1, b)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.bm25_free(self._h)
            self._h = None

    def add(self, key: int, text: str) -> None:
        self._lib.bm25_add(self._h, key, text.encode("utf-8", "replace"))

    def remove(self, key: int) -> None:
        self._lib.bm25_remove(self._h, key)

    def __len__(self) -> int:
        return self._lib.bm25_len(self._h)

    def search(self, query: str, k: int) -> list[tuple[int, float]]:
        n = max(k, 0)
        keys = (ctypes.c_int64 * n)()
        scores = (ctypes.c_double * n)()
        got = self._lib.bm25_search(
            self._h, query.encode("utf-8", "replace"), n, keys, scores
        )
        return [(keys[i], scores[i]) for i in range(got)]


_METRICS = {"cos": 0, "l2sq": 1, "ip": 2, "dot": 2}


class NativeHnsw:
    """ctypes wrapper over the C++ HNSW ANN index (usearch equivalent)."""

    def __init__(self, dim: int, metric: str = "cos", *, M: int = 16,
                 ef_build: int = 128, ef_search: int = 64):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.dim = dim
        self._h = lib.hnsw_new(dim, _METRICS[metric], M, ef_build, ef_search)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.hnsw_free(self._h)
            self._h = None

    def add(self, key: int, vec) -> None:
        v = np.ascontiguousarray(vec, dtype=np.float32)
        self._lib.hnsw_add(
            self._h, key, v.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        )

    def add_batch(self, keys, vecs) -> None:
        """Insert n rows in ONE library crossing (ISSUE 16: the
        one-doc-per-dispatch ann build was dominated by per-row call
        overhead)."""
        ks = np.ascontiguousarray(keys, dtype=np.int64)
        vs = np.ascontiguousarray(vecs, dtype=np.float32)
        if vs.ndim != 2 or vs.shape[0] != ks.shape[0]:
            raise ValueError("keys/vectors shape mismatch")
        self._lib.hnsw_add_batch(
            self._h,
            ks.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            vs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ks.shape[0],
        )

    def remove(self, key: int) -> None:
        self._lib.hnsw_remove(self._h, key)

    def __len__(self) -> int:
        return self._lib.hnsw_len(self._h)

    def search(self, vec, k: int) -> list[tuple[int, float]]:
        v = np.ascontiguousarray(vec, dtype=np.float32)
        n = max(k, 0)
        keys = (ctypes.c_int64 * n)()
        scores = (ctypes.c_double * n)()
        got = self._lib.hnsw_search(
            self._h, v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, keys, scores,
        )
        return [(keys[i], scores[i]) for i in range(got)]
