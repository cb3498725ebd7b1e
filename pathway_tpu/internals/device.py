"""Device plane of the flight recorder (ISSUE 15).

The host-side observability stack (PRs 8/10: flight recorder, cluster
observatory, critical-path analyzer) goes dark at every JAX dispatch:
an ``ExternalIndexNode`` KNN scan or an embedder forward is one opaque
slab of node self-time, with no way to tell whether a slow node needs a
kernel (device-bound) or needs the host path fixed (device idle while
the host assembles batches). This module is the missing plane: engine
dispatch sites (ops/knn.py, parallel/sharded_knn.py, models/encoder.py,
models/decoder.py, the serving gateway's fused window dispatch) wrap
every device launch in a **timed dispatch record** —

* wall span of the whole dispatch (host assembly + enqueue + wait);
* ``jax.block_until_ready``-bounded device time (enqueue-return to
  results-ready — the device's share of the wall span);
* compiled ``cost_analysis()`` FLOPs / bytes-accessed when obtainable
  (cached per shape key; analytical cost models are the fallback, so a
  backend without cost analysis still produces honest numbers);
* host<->device transfer bytes and the dispatch-queue depth at launch;
* the ENCLOSING ENGINE NODE (runtime step context), so device spans in
  the merged Perfetto trace correlate to their node span by dispatch id.

Records feed three consumers: the flight recorder's new per-rank
**device tracks** (internals/flight.py ``note_dispatch``), the
OpenMetrics ``device_*`` families + ``device_mfu`` /
``device_hbm_{live,peak}_bytes`` gauges (internals/monitoring.py,
aggregated into ``/metrics/cluster``), and the roofline verdicts of
``--profile`` / ``--critical-path`` (analysis/profile.py consumes the
same pure ``roofline_verdict`` below — no drift).

Discipline: ``PLANE.begin`` / ``end`` are the one hook at a dispatch
site. They always record the site's span into the always-on ring
(internals/flight.py: two clock reads and a tuple append, and the span
on a running jax.profiler session's host plane); the timed record —
the ``block_until_ready`` sync, the compiled-cost lookup, the metrics
— is made only while the runtime's profiling plane is armed
(``PATHWAY_TRACE`` or a live /metrics endpoint): an armed run trades
dispatch-pipelining for attribution, the documented cost. The guard
lives in ``end``, not at the sites.

This module never imports jax at module scope: the relational plane
(and the ASan/fork CI lanes, where importing jaxlib is fatal) must be
able to load it for free.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time as _time
from typing import Any

# the span ring (internals/flight.py), bound at the first dispatch: this
# file also loads alone, by path, with no package around it
_flight = None


def _ring():
    global _flight
    from pathway_tpu.internals import flight

    _flight = flight
    return flight


# -- peak-rate tables --------------------------------------------------------
# per-device-kind peak dense FLOP/s (bf16 MXU) and HBM bandwidth. Used as
# the MFU denominator and the roofline ridge; PATHWAY_DEVICE_PEAK_FLOPS /
# PATHWAY_DEVICE_PEAK_GBPS override for hardware the table has not met.
# Substring-matched against jax's device_kind, most specific first.
_PEAK_TABLE: tuple[tuple[str, float, float], ...] = (
    # (device_kind substring, peak FLOP/s, peak HBM bytes/s)
    ("v6", 918e12, 1638e9),   # TPU v6e (Trillium)
    ("v5p", 459e12, 2765e9),
    ("v5", 197e12, 819e9),    # v5e / "TPU v5 lite"
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)
# CPU / unknown backend: a deliberately modest single-chip estimate so
# CPU-lane MFU numbers read as a sanity signal, not hardware truth
_PEAK_FLOPS_FALLBACK = 2e11
_PEAK_BW_FALLBACK = 50e9

_HOST_BOUND_SHARE_DEFAULT = 0.35


def _env_float(name: str) -> float | None:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw.strip() else None
    except ValueError:
        return None


def _env_off(name: str) -> bool:
    return str(os.environ.get(name, "1")).strip().lower() in (
        "0", "false", "no",
    )


def device_kind() -> str:
    """The local device's kind string — only when jax is ALREADY loaded
    (this plane must never be the reason jax imports)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return ""
    try:
        devs = jax.local_devices()
        return str(devs[0].device_kind) if devs else ""
    except Exception:
        return ""


def peak_flops(kind: str | None = None) -> float:
    """MFU denominator: PATHWAY_DEVICE_PEAK_FLOPS, else the device-kind
    table, else the CPU fallback."""
    override = _env_float("PATHWAY_DEVICE_PEAK_FLOPS")
    if override is not None:
        return override
    kind = device_kind() if kind is None else kind
    low = kind.lower()
    for sub, fl, _bw in _PEAK_TABLE:
        if sub in low:
            return fl
    return _PEAK_FLOPS_FALLBACK


def peak_bandwidth(kind: str | None = None) -> float:
    """Roofline ridge denominator (bytes/s): PATHWAY_DEVICE_PEAK_GBPS
    (GB/s), else the device-kind table, else the CPU fallback."""
    override = _env_float("PATHWAY_DEVICE_PEAK_GBPS")
    if override is not None:
        return override * 1e9
    kind = device_kind() if kind is None else kind
    low = kind.lower()
    for sub, _fl, bw in _PEAK_TABLE:
        if sub in low:
            return bw
    return _PEAK_BW_FALLBACK


def host_bound_share() -> float:
    """Device-busy share of a dispatch site's wall below which the site
    reads host-bound (PATHWAY_DEVICE_HOST_BOUND_SHARE)."""
    v = _env_float("PATHWAY_DEVICE_HOST_BOUND_SHARE")
    if v is None or not (0.0 <= v <= 1.0):
        return _HOST_BOUND_SHARE_DEFAULT
    return v


def roofline_verdict(
    wall_s: float,
    device_s: float,
    flops: float,
    bytes_accessed: float,
    pk_flops: float | None = None,
    pk_bw: float | None = None,
    host_share: float | None = None,
) -> str:
    """The per-site/per-node verdict of the device plane, pure so the
    offline analyzers (analysis/profile.py, analysis/critical_path.py)
    and the live plane compute the SAME answer:

    * ``host-bound`` — the device was idle for most of the dispatch wall
      (the host was assembling batches / expanding rows): fixing this
      node means fixing the host path, not writing a kernel;
    * ``compute-bound`` — arithmetic intensity (FLOPs per HBM byte) at
      or above the roofline ridge: the MXU is the limiter, a faster
      kernel or lower precision is the lever;
    * ``bandwidth-bound`` — intensity below the ridge: HBM traffic is
      the limiter (fuse, cache, or shrink the working set).
    """
    share = host_bound_share() if host_share is None else host_share
    if wall_s > 0 and device_s < share * wall_s:
        return "host-bound"
    if flops <= 0:
        # no modeled device arithmetic at all: whatever time this site
        # took was host work by definition
        return "host-bound"
    if bytes_accessed <= 0:
        return "compute-bound"
    pf = peak_flops() if pk_flops is None else pk_flops
    pb = peak_bandwidth() if pk_bw is None else pk_bw
    ridge = pf / max(pb, 1.0)
    return (
        "compute-bound"
        if (flops / bytes_accessed) >= ridge
        else "bandwidth-bound"
    )


def mfu(flops: float, device_s: float, pk_flops: float | None = None) -> float:
    """Model FLOPs utilization of a dispatch set: achieved FLOP/s over
    the device-kind peak. Callers pick which FLOPs they feed: padded
    FLOPs (what the hardware executed, including bucket padding) or
    effective FLOPs (only real rows/tokens — the honest utilization
    number ISSUE 16 reports as ``device_mfu``, with the padded variant
    kept alongside as ``device_mfu_padded``)."""
    if device_s <= 0 or flops <= 0:
        return 0.0
    return (flops / device_s) / (peak_flops() if pk_flops is None else pk_flops)


# -- device memory (absent-stat-safe) ----------------------------------------

def memory_stats() -> dict | None:
    """``jax.local_devices()[0].memory_stats()`` with every absence mode
    folded to None: jax not imported, no devices, the backend has no
    allocator stats (CPU), or the call raises. Callers must treat None
    as "no HBM story on this backend", not an error."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        devs = jax.local_devices()
        if not devs:
            return None
        ms = devs[0].memory_stats()
        return ms if ms else None
    except Exception:
        return None


def platform_info() -> dict | None:
    """Trace metadata: what hardware this rank actually measured —
    backend platform, device kind and the peak rates the MFU/roofline
    numbers were computed against. None when jax never loaded in this
    process (a pure relational run has no device story). Embedded into
    the trace's ``rank_meta`` so a merged multi-rank file says per rank
    what it ran on (ISSUE 15 satellite)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        backend = jax.default_backend()
    except Exception:
        backend = "?"
    kind = device_kind()
    return {
        "backend": backend,
        "device_kind": kind,
        "peak_flops": peak_flops(kind),
        "peak_bandwidth": peak_bandwidth(kind),
    }


# -- persistent compile cache -------------------------------------------------

# the checkout this package was loaded from: pathway_tpu/internals/device.py
# -> three levels up. Derived from the file path, not from git — the copy
# on the chip machine is not a repository.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home before the
    first compile; returns the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
    nothing. Unset: ``<checkout>/.jax_cache`` — the directory is part of
    the cache key, so it is never a temp name, a pid or a time. A
    process pinned to the CPU (``JAX_PLATFORMS=cpu``: tests, CI lanes)
    gets no cache: XLA:CPU logs a machine-feature mismatch error on
    every reload of its own cached executable, and nothing at test size
    compiles long enough to be kept. Called at import by
    models/encoder.py and ops/topk.py — every dense-plane module
    imports one of them (``pathway_tpu.ops`` and ``pathway_tpu.parallel``
    load topk from their ``__init__``) and both import jax at module
    scope themselves: this function is never the reason jax loads into
    a relational-only process."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.config.jax_platforms == "cpu":
        return ""
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- compiled cost analysis (cached per shape key) ---------------------------

_COST_CACHE: dict = {}


def _cost_cache_cap() -> int:
    """PATHWAY_DEVICE_COST_CACHE_CAP: entry bound on the per-shape-key
    compiled-cost cache. Well-behaved sites keep bounded shape sets by
    design, but an adversarial shape stream (a bucket leak upstream of
    the cost lookup) would otherwise grow the cache without limit —
    eviction is insertion-ordered (oldest shape key first)."""
    raw = os.environ.get("PATHWAY_DEVICE_COST_CACHE_CAP", "")
    try:
        v = int(raw) if raw.strip() else 512
    except ValueError:
        v = 512
    return max(1, v)


def compiled_cost(
    key: tuple,
    fn: Any,
    args: tuple,
    fallback: tuple[float, float],
) -> tuple[float, float]:
    """``(flops, bytes_accessed)`` for a jitted callable at one shape,
    preferring the compiled executable's own ``cost_analysis()`` and
    falling back to the caller's analytical model. Cached per ``key`` —
    dispatch sites keep bounded shape sets by design (pow2 batch
    buckets, capacity doublings), so the AOT lower+compile runs once
    per shape, not per dispatch; the cache itself is bounded (ISSUE 20:
    ``PATHWAY_DEVICE_COST_CACHE_CAP``, oldest-first eviction) so an
    adversarial shape stream cannot grow it without limit. ``fn=None``
    skips the attempt entirely (sites whose executables are too big to
    recompile for bookkeeping, e.g. the 1M-row KNN scan).
    """
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    flops, nbytes = float(fallback[0]), float(fallback[1])
    if fn is not None and not _env_off("PATHWAY_DEVICE_COST_ANALYSIS"):
        try:
            ca = fn.lower(*args).compile().cost_analysis()
            ca_flops = float(ca.get("flops", 0.0) or 0.0)
            ca_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
            if ca_flops > 0:
                flops = ca_flops
            if ca_bytes > 0:
                nbytes = ca_bytes
        except Exception:
            pass
    cap = _cost_cache_cap()
    while len(_COST_CACHE) >= cap:
        _COST_CACHE.pop(next(iter(_COST_CACHE)))
    _COST_CACHE[key] = (flops, nbytes)
    return flops, nbytes


def nbytes_of(*arrays: Any) -> int:
    """Sum of ``nbytes`` over array-likes (None / scalar leaves are
    free) — the transfer-bytes estimate dispatch sites report."""
    total = 0
    for a in arrays:
        n = getattr(a, "nbytes", None)
        if n is not None:
            try:
                total += int(n)
            except (TypeError, ValueError):
                pass
    return total


# -- device-site registry (ISSUE 20) -----------------------------------------
# Every dispatch site declares itself here at import time: its analytical
# cost model, the dtypes its device buffers carry, which inputs it donates
# and where the dispatch lives. The Device Doctor (analysis/device_plan.py)
# walks THIS registry — not a parallel hand-maintained list — so a site
# added in ops/ without a registration is registry drift, caught by
# scripts/lint_gil.py pass 4.


@dataclasses.dataclass(frozen=True)
class DeviceSite:
    """One registered device-dispatch site.

    ``cost_model`` is the SAME callable the runtime site feeds into its
    dispatch records (``-> (flops, bytes_accessed)``) — the anti-drift
    contract: analyzer predictions and runtime attribution compute from
    one object. ``donates`` names the buffers the site's jitted callable
    donates (empty for read-only / host-only sites)."""

    name: str
    cost_model: Any
    dtypes: tuple
    where: str = ""
    donates: tuple = ()
    description: str = ""


_SITE_REGISTRY: dict[str, DeviceSite] = {}


def device_site(
    name: str,
    *,
    cost_model: Any,
    dtypes: Any,
    where: str = "",
    donates: Any = (),
    description: str = "",
) -> DeviceSite:
    """Register (or re-register — module reloads are idempotent) one
    dispatch site. Keyword-only by design: lint_gil pass 4 checks every
    registration names its ``cost_model=`` and ``dtypes=`` explicitly."""
    site = DeviceSite(
        name, cost_model, tuple(dtypes), where, tuple(donates), description
    )
    _SITE_REGISTRY[name] = site
    return site


def registered_sites() -> dict[str, DeviceSite]:
    """Snapshot of the registry (name -> DeviceSite)."""
    return dict(_SITE_REGISTRY)


# -- shared shape-bucket models (ISSUE 20) -----------------------------------
# The bucket functions the dispatch sites pad with ARE the functions the
# retrace audit enumerates with (the eligibility.py discipline: predicates
# the analyzer gates on are the objects the runtime consumes). Sites alias
# these — tests pin the identities — so the predicted shape-bucket set and
# the runtime's seen-bucket keys cannot drift.


def batch_bucket(n: int, floor: int, cap: int) -> int:
    """Pow2 batch bucket from ``floor``, capped — the encoder's batch
    padding (models/encoder.py ``pad_batch``)."""
    b = floor
    while b < n and b < cap:
        b *= 2
    return min(b, cap)


def seq_bucket(L: int, cap: int) -> int:
    """The encoder's sequence padding: the narrowest width of the ladder
    32, 64, 128, ... (doubling, capped) that holds ``L`` tokens. One
    ladder for every dispatch: a call's only one and a group of a call
    that was cut (``encoder_group_shapes``) alike, so a process meets
    one executable a rung and no more."""
    width = min(32, cap)
    while width < min(L, cap):
        width = min(width * 2, cap)
    return width


def pow2_capacity(n: int, floor: int = 128) -> int:
    """Pow2 index capacity from the 128-slot floor — KnnShard's growth
    schedule (each distinct capacity is a fresh XLA executable)."""
    p = floor
    while p < n:
        p *= 2
    return p


def query_pad(n: int) -> int:
    """Pow2 query-batch padding from 1 — the search sites' batch set."""
    p = 1
    while p < n:
        p *= 2
    return p


def knn_search_bucket(
    n: int, capacity: int, k: int, chunk: int | None
) -> tuple:
    """Compiled-shape key of one ``knn.search`` dispatch: (padded query
    batch, capacity, effective k). Effective k mirrors the site's own
    clamp — top_k per scored block cannot exceed the block width."""
    k_eff = min(k, capacity, chunk or 8192)
    return (query_pad(n), capacity, k_eff)


def knn_write_bucket(nrows: int, capacity: int) -> tuple:
    """Compiled-shape key of one ``knn.write`` slot-write dispatch. The
    row count is data-driven (writes are not padded), so an unbounded
    write-batch-size distribution IS an unbounded executable set — the
    retrace audit flags exactly that."""
    return (nrows, capacity)


def sharded_search_bucket(
    n: int, n_shards: int, local_cap: int, k: int, chunk: int | None
) -> tuple:
    """Compiled-shape key of one ``knn.sharded_search`` dispatch —
    effective k mirrors ShardedKnnIndex.search's clamp (per-shard
    partial k capped by shard rows, merged up to total capacity)."""
    k_eff = min(k, n_shards * min(local_cap, chunk or local_cap))
    return (query_pad(n), n_shards * local_cap, k_eff)


def sharded_write_bucket(nrows: int, capacity: int) -> tuple:
    """Compiled-shape key of one ``knn.sharded_write`` dispatch."""
    return (nrows, capacity)


def encoder_bucket(nb: int, Lb: int, compact: bool) -> tuple:
    """Compiled-shape key of one ``encoder.forward`` dispatch."""
    return (nb, Lb, bool(compact))


# A group of a length-sorted ``SentenceEncoder.encode`` call holds this
# share of the full ``batch_size x max_len`` slab: 4,096 tokens at
# 256 x 512. Picked on the chip (PERF.md section 6, PR 28).
ENCODER_GROUP_SHARE = 32
# ...and no more rows than this. The narrow members (64 x 32, 64 x 64 at
# the published sizes) are then shapes a process that answers questions
# warms anyway, so its documents bring three executables of their own
# and not four (PERF.md section 6, PR 29).
ENCODER_GROUP_ROWS = 64


def encoder_group_shapes(batch_cap: int, max_len: int) -> tuple:
    """Every ``(rows, width)`` a ``SentenceEncoder.encode`` call dispatches
    when its rows do not fit one dispatch, narrowest first: the widths of
    ``seq_bucket``'s ladder up to ``max_len``, each with the pow2 row
    count that fills the group's token budget (8 at least; at most
    ``ENCODER_GROUP_ROWS`` and ``batch_cap``). A closed set, fixed by the
    two arguments alone: a group short of rows, a call's last, is padded
    up to its member, so a call's lengths choose among the members and
    never add one."""
    budget = batch_cap * max_len // ENCODER_GROUP_SHARE
    most = min(batch_cap, ENCODER_GROUP_ROWS)
    shapes, width = [], seq_bucket(1, max_len)
    while True:
        rows = min(8, batch_cap)
        while rows * 2 * width <= budget and rows * 2 <= most:
            rows *= 2
        shapes.append((rows, width))
        if width >= max_len:
            return tuple(shapes)
        width = seq_bucket(width + 1, max_len)


def encoder_call_groups(extents, batch_cap: int, max_len: int) -> list:
    """The dispatches of one ``encode`` call as ``(first, stop, rows,
    width)`` over its rows ordered longest first (``extents``: each row's
    token count in that order). The longest row not yet placed opens a
    group at the member of :func:`encoder_group_shapes` of its width and
    takes that member's rows. A call that one member holds whole is one
    dispatch at that width and the pow2 bucket of its own row count:
    ``pad_batch``'s shape, what it was before calls were cut."""
    rows_at = {width: rows for rows, width in encoder_group_shapes(batch_cap, max_len)}
    n = len(extents)
    groups, at = [], 0
    while at < n:
        width = seq_bucket(int(extents[at]), max_len)
        rows = rows_at[width]
        if at == 0 and n <= rows:
            return [(0, n, batch_bucket(n, 8, batch_cap), width)]
        groups.append((at, min(at + rows, n), rows, width))
        at += rows
    return groups


def encoder_call_shapes(
    n: int, longest: int, batch_cap: int, max_len: int
) -> set:
    """Every ``(rows, width)`` an ``encode`` call of ``n`` rows, the
    longest of ``longest`` tokens, can dispatch: what the retrace audit
    lists for a declared call, whose other rows' lengths it is not told."""
    groups = encoder_call_groups([longest] * n, batch_cap, max_len)
    shapes = {(rows, width) for _, _, rows, width in groups}
    if len(groups) > 1:  # a shorter row may open any narrower member
        shapes.update(
            s for s in encoder_group_shapes(batch_cap, max_len)
            if s[1] <= groups[0][3]
        )
    return shapes


# -- static HBM budget (ISSUE 20) --------------------------------------------
# Per-device-kind HBM capacity for the Device Doctor's static footprint
# check; PATHWAY_DEVICE_HBM_BYTES overrides (the CPU/CI lever — model a
# v5e budget on a devbox), allocator stats win when the backend has them.
_HBM_TABLE: tuple[tuple[str, float], ...] = (
    ("v6", 32e9),
    ("v5p", 95e9),
    ("v5", 16e9),
    ("v4", 32e9),
    ("v3", 32e9),
    ("v2", 16e9),
)
_HBM_FALLBACK = 8 * 1024**3


def device_hbm_bytes(kind: str | None = None) -> int:
    """Per-chip HBM budget in bytes: ``PATHWAY_DEVICE_HBM_BYTES`` wins,
    then the backend's own allocator limit, then the device-kind table,
    then a deliberately small 8 GiB fallback (CPU/CI: the budget check
    still means something on a host with no HBM story)."""
    raw = os.environ.get("PATHWAY_DEVICE_HBM_BYTES", "")
    if raw.strip():
        try:
            v = int(float(raw))
            if v > 0:
                return v
        except ValueError:
            pass
    ms = memory_stats()
    if ms is not None:
        try:
            lim = int(ms.get("bytes_limit", 0) or 0)
        except (TypeError, ValueError):
            lim = 0
        if lim > 0:
            return lim
    kind = device_kind() if kind is None else kind
    low = kind.lower()
    for sub, b in _HBM_TABLE:
        if sub in low:
            return int(b)
    return _HBM_FALLBACK


def index_shard_bytes(capacity: int, dim: int, *, donated: bool = True) -> float:
    """Steady-state HBM of one index shard's buffer triple: f32 vectors
    [capacity, dim] + bool valid [capacity] + f32 sq_norms [capacity].
    An UN-donated write keeps the old triple alive across the dispatch
    — the doctor's donation audit bills exactly this doubling."""
    steady = 4.0 * capacity * dim + 1.0 * capacity + 4.0 * capacity
    return steady if donated else 2.0 * steady


def snapshot_staging_bytes(capacity: int, dim: int) -> float:
    """Worst-case staging of an epoch-aligned index snapshot cut: one
    host-bound copy of the buffer triple in flight."""
    return 4.0 * capacity * dim + 1.0 * capacity + 4.0 * capacity


# -- the plane ---------------------------------------------------------------


class _Dispatch:
    """One in-flight dispatch (``PLANE.begin`` ... ``end``): always its
    ring span (internals/flight.py), and the timed record's fields when
    the plane was armed at ``begin``."""

    __slots__ = (
        "site", "span", "armed", "seq", "node", "t_commit", "t0", "t_ret",
        "t_done", "depth",
    )

    def __init__(self, site: str, span):
        self.site = site
        self.span = span
        self.armed = False


class DevicePlane:
    """Process-wide device-dispatch recorder.

    Armed/disarmed by the runtime around each run (like the native
    trace rings, the plane is process-global: under the emulated-rank
    CI lane several thread-ranks share it and rank 0's recorder claims
    the records — approximate there, exact on real multi-rank meshes).
    ``on`` is what ``begin`` checks: off, a dispatch is its ring span
    and nothing more.
    """

    # memory_stats() walks the allocator — sample at most this often
    _MEM_POLL_S = 0.5

    def __init__(self):
        self.on = False
        self.recorder = None
        self.stats = None
        self._seq = 0
        self._inflight = 0
        self._lock = threading.Lock()
        self._node_ctx = threading.local()
        self._last_mem_poll = 0.0

    # -- lifecycle (runtime) ----------------------------------------------
    def arm(self, recorder, stats) -> None:
        """Attach this run's flight recorder (may be None: metrics-only
        runs still feed the gauges) and ProberStats. PATHWAY_DEVICE_TRACE=0
        keeps the plane off even on an armed run — the opt-out for
        pipelines where the per-dispatch ``block_until_ready`` sync
        costs more than the visibility buys."""
        if _env_off("PATHWAY_DEVICE_TRACE"):
            return
        self.recorder = recorder
        self.stats = stats
        if stats is not None:
            stats.set_device_peak_flops(peak_flops())
        self._last_mem_poll = 0.0
        # a dispatch site that raised between begin() and end() in a
        # PREVIOUS run left its record open — re-zero so queue-depth
        # reporting starts honest for this run
        with self._lock:
            self._inflight = 0
        self.on = True

    def disarm(self) -> None:
        self.on = False
        self.recorder = None
        self.stats = None

    # -- engine-node context (runtime step loop) --------------------------
    def set_node(self, nid: int, t_commit: int) -> None:
        self._node_ctx.v = (nid, t_commit)

    def clear_node(self) -> None:
        self._node_ctx.v = None

    def _current_node(self):
        return getattr(self._node_ctx, "v", None)

    # -- dispatch records --------------------------------------------------
    def begin(self, site: str, *, span: str | None = None,
              **args) -> _Dispatch:
        """Open a dispatch: always a ring span named ``span`` (the site's
        name unless given) with ``args``; when the plane is armed, also
        the timed record that ``end`` blocks on and feeds to the flight
        recorder and the device metrics. One hook a site."""
        d = _Dispatch(site, (_flight or _ring()).span(span or site, **args))
        d.span.__enter__()
        if not self.on:
            return d
        with self._lock:
            self._seq += 1
            d.seq = self._seq
            self._inflight += 1
            d.depth = self._inflight
        ctx = self._current_node()
        d.node, d.t_commit = ctx if ctx is not None else (None, None)
        d.t0 = d.t_ret = d.t_done = _time.perf_counter_ns()
        d.armed = True
        return d

    def enqueued(self, d: _Dispatch | None) -> None:
        """Mark the enqueue boundary explicitly (optional — ``end``
        stamps it from its ``t_ret`` argument path otherwise)."""
        if d is not None and d.armed:
            d.t_ret = _time.perf_counter_ns()

    def end(
        self,
        d: _Dispatch | None,
        outputs: Any = None,
        *,
        flops: float = 0.0,
        flops_effective: float | None = None,
        bytes_accessed: float = 0.0,
        transfer_bytes: int = 0,
        block: bool = True,
        cost_fn: Any = None,
        effective_share: float | None = None,
    ) -> None:
        """Close a dispatch record: ``outputs`` (a jax array / pytree)
        is blocked on so the device time is bounded, the record lands on
        the flight recorder's device track and the OpenMetrics device
        families. Host-only dispatch sites (the serving gateway's window
        commit) pass ``outputs=None, block=False`` — wall-only records
        whose device time is honestly zero. ``cost_fn`` (-> (flops,
        bytes_accessed)) runs AFTER the wall span is stamped — the home
        for ``compiled_cost``, whose first call per shape bucket pays an
        AOT lower+compile that must not be charged into the record as
        host time.

        MFU honesty (ISSUE 16): ``flops`` is what the hardware executed
        — padded rows/tokens included. ``flops_effective`` is the share
        of it spent on REAL rows; sites that pad batches to pow2
        buckets pass it (or ``effective_share`` in [0, 1], applied
        after ``cost_fn`` resolves the padded number) so bucket-padding
        waste is visible instead of inflating the MFU gauge. Defaults
        to ``flops`` — an unpadded site is 100% effective."""
        if d is None:
            return
        # the ring span closes where the site's own work ends, armed or
        # not: what follows (the block, the cost lookup) is the plane's
        d.span.__exit__(None, None, None)
        if not d.armed:
            return
        if d.t_ret == d.t0:
            d.t_ret = _time.perf_counter_ns()
        if block and outputs is not None:
            jax = sys.modules.get("jax")
            if jax is not None:
                try:
                    jax.block_until_ready(outputs)
                except Exception:
                    pass
        d.t_done = _time.perf_counter_ns()
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
        if cost_fn is not None:
            try:
                flops, bytes_accessed = cost_fn()
            except Exception:
                pass
        if flops_effective is None:
            flops_effective = (
                flops * min(max(effective_share, 0.0), 1.0)
                if effective_share is not None
                else flops
            )
        flops_effective = min(flops_effective, flops)
        wall_s = max(0, d.t_done - d.t0) / 1e9
        device_s = max(0, d.t_done - d.t_ret) / 1e9
        stats = self.stats
        if stats is not None:
            stats.on_device_dispatch(
                d.site, wall_s, device_s, flops, bytes_accessed,
                transfer_bytes, d.depth, flops_effective,
            )
        rec = self.recorder
        if rec is not None:
            rec.note_dispatch(
                d.site, d.seq, d.node, d.t_commit, d.t0, d.t_ret,
                d.t_done, flops, bytes_accessed, transfer_bytes, d.depth,
                flops_effective,
            )
        self._sample_memory_throttled()

    def note_recompile(self, site: str) -> None:
        """One fresh XLA compilation observed at a dispatch site (a new
        shape bucket entered its compiled-fn cache). Feeds the
        ``device_recompiles_total`` counter so a silent recompile storm
        — a shape-bucket leak re-lowering every batch — shows on the
        TUI/cluster view instead of only as mysterious wall time."""
        stats = self.stats
        if stats is not None:
            stats.on_device_recompile(site)

    # -- HBM gauges --------------------------------------------------------
    def _sample_memory_throttled(self) -> None:
        now = _time.monotonic()
        if now - self._last_mem_poll < self._MEM_POLL_S:
            return
        self._last_mem_poll = now
        self.sample_memory()

    def sample_memory(self) -> None:
        """Pull ``memory_stats()`` into the HBM gauges; a backend with
        no allocator stats (CPU) leaves the gauges at their absent-safe
        zeros with ``available`` false."""
        stats = self.stats
        if stats is None:
            return
        ms = memory_stats()
        if ms is None:
            stats.set_device_memory(0, 0, available=False)
            return
        stats.set_device_memory(
            int(ms.get("bytes_in_use", 0) or 0),
            int(ms.get("peak_bytes_in_use", 0) or 0),
            available=True,
        )


PLANE = DevicePlane()


# -- dispatch supervision (ISSUE 17, device fault domain) --------------------
# Before this, a device dispatch had exactly two outcomes: success, or an
# exception that killed the whole pipelined run (with a hung dispatch only
# dying by the 300s MeshTimeout backstop). Supervised sites route their
# launch through :func:`supervised_dispatch`: failures are classified
# (transient / oom / permanent) and the pure
# ``protocol.device_dispatch_decide`` transition picks retry-with-backoff,
# brownout, or epoch abort — the connector ``SupervisorPolicy`` semantics
# (io/_connector.py) applied to the device plane. An optional watchdog
# deadline (``PATHWAY_DEVICE_DISPATCH_TIMEOUT_S``; 0 = off, the default —
# the hot path stays a plain call) bounds a hung dispatch well under the
# mesh op timeout.

_RETRY_BACKOFF_BASE_S = 0.05
_RETRY_BACKOFF_CAP_S = 2.0

# transient XLA/runtime failure markers: worth a bounded retry. OOM
# markers are matched FIRST — RESOURCE_EXHAUSTED must never retry into
# the same full allocator.
_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory",
                "oom ", "allocating ")
_TRANSIENT_MARKERS = (
    "unavailable", "deadline_exceeded", "deadline exceeded", "aborted",
    "connection reset", "temporarily", "try again", "internal: failed",
)
# a failed dispatch may have consumed its donated input buffers — a
# retry would compute on deleted arrays; classify as permanent so the
# epoch rolls back to buffers the snapshot actually holds.
# The second row is libtpu 0.0.34's own wording, seen on a v5e (PR 21):
# a kernel whose window outgrows VMEM fails to COMPILE with
#   "RESOURCE_EXHAUSTED: Allocation (size=..) would exceed memory
#    (size=134217728) :: #allocation7 [shape = 'u8[..]', space=vmem, .."
# — deterministic, so it must abort rather than read as HBM pressure
# and brown the gateway out; and a chip held by another process is
#   "ABORTED: The TPU is already in use by process with pid N" or, when
#   two start at once, "ABORTED: Internal error when accessing libtpu
#   multi-process lockfile" (both under "Unable to initialize backend")
# — no retry frees it. HBM exhaustion reads "RESOURCE_EXHAUSTED: Error
# allocating device buffer: Attempting to allocate 24.00G. ..(0x0x0_HBM0)"
# and stays with the OOM markers.
_PERMANENT_MARKERS = (
    "donated", "deleted", "invalid buffer",
    "space=vmem", "already in use", "lockfile",
    "unable to initialize backend",
)


class DeviceOom(RuntimeError):
    """HBM exhaustion (real RESOURCE_EXHAUSTED or injected
    ``device.oom``): growth was refused, the index keeps serving at its
    committed capacity and the serving breaker browns out."""


class WatchdogTimeout(RuntimeError):
    """A supervised dispatch exceeded PATHWAY_DEVICE_DISPATCH_TIMEOUT_S.
    The hung launch thread is abandoned (XLA offers no cancel); the
    caller's epoch aborts well under the mesh op timeout backstop."""


def classify_device_error(exc: BaseException) -> str:
    """``"transient"`` | ``"oom"`` | ``"permanent"`` — the input to the
    pure ``device_dispatch_decide`` transition. Injected faults carry
    their class explicitly (``device.oom`` point -> oom, ``retryable``
    -> transient); real errors classify by message markers, permanent
    winning on donation/deletion evidence (retrying on consumed buffers
    can only corrupt)."""
    from pathway_tpu.internals.faults import InjectedFault

    if isinstance(exc, WatchdogTimeout):
        return "permanent"
    if isinstance(exc, InjectedFault):
        if exc.point == "device.oom":
            return "oom"
        return "transient" if exc.retryable else "permanent"
    if isinstance(exc, MemoryError):
        return "oom"
    low = f"{type(exc).__name__}: {exc}".lower()
    if any(m in low for m in _PERMANENT_MARKERS):
        return "permanent"
    if any(m in low for m in _OOM_MARKERS):
        return "oom"
    if any(m in low for m in _TRANSIENT_MARKERS):
        return "transient"
    return "permanent"


def dispatch_timeout_s() -> float:
    """Watchdog deadline for supervised dispatches; 0 disables (the
    default: unsupervised hangs still die by the mesh op timeout)."""
    v = _env_float("PATHWAY_DEVICE_DISPATCH_TIMEOUT_S")
    return v if v is not None and v > 0 else 0.0


def dispatch_retries() -> int:
    raw = os.environ.get("PATHWAY_DEVICE_RETRIES", "")
    try:
        v = int(raw) if raw.strip() else 2
    except ValueError:
        v = 2
    return max(0, v)


# serving-plane OOM listeners: the HTTP gateway registers a callback
# that flips its breaker into brownout (answers `Degraded: true` from
# the last committed index) the moment any device site reports OOM
_OOM_LISTENERS: list = []
_OOM_LOCK = threading.Lock()


def on_oom(listener) -> None:
    with _OOM_LOCK:
        if listener not in _OOM_LISTENERS:
            _OOM_LISTENERS.append(listener)


def remove_oom_listener(listener) -> None:
    with _OOM_LOCK:
        if listener in _OOM_LISTENERS:
            _OOM_LISTENERS.remove(listener)


def notify_oom(site: str) -> None:
    """Tick the oom counter and brown out every registered serving
    gateway. Listener errors are swallowed — OOM handling must never
    make the failure worse."""
    stats = PLANE.stats
    if stats is not None:
        stats.on_device_oom(site)
    with _OOM_LOCK:
        listeners = list(_OOM_LISTENERS)
    for listener in listeners:
        try:
            listener(site)
        except Exception:
            pass


def _run_with_watchdog(site: str, thunk, timeout: float):
    """Run the launch on a worker thread with a deadline. A trip
    abandons the hung thread (daemon) — the record is the
    ``device_watchdog_trips_total`` counter plus the raised
    :class:`WatchdogTimeout`, which classifies permanent so the epoch
    aborts instead of waiting out the 300s mesh backstop."""
    box: list = []

    def worker():
        try:
            box.append(("ok", thunk()))
        except BaseException as e:  # noqa: BLE001 - re-raised on the caller
            box.append(("err", e))

    t = threading.Thread(
        target=worker, name=f"device-dispatch:{site}", daemon=True
    )
    t.start()
    t.join(timeout)
    if not box:
        stats = PLANE.stats
        if stats is not None:
            stats.on_device_watchdog_trip(site)
        raise WatchdogTimeout(
            f"device dispatch at {site} exceeded the "
            f"{timeout:g}s watchdog deadline"
        )
    status, value = box[0]
    if status == "err":
        raise value
    return value


def supervised_dispatch(site: str, thunk):
    """Run one device launch under supervision: the ``device.dispatch``
    fault point fires first (with ``site=`` context), then the thunk;
    classified failures take the ``device_dispatch_decide`` verdict —
    bounded-backoff retry, OOM brownout, or abort. Idempotence contract:
    the thunk must be safe to re-run (searches are; writes are upserts
    whose donation failures classify permanent)."""
    from pathway_tpu.internals import faults as _faults
    from pathway_tpu.parallel import protocol as _proto

    timeout = dispatch_timeout_s()
    retries = dispatch_retries()
    attempt = 0
    while True:
        try:
            _faults.fault_point("device.dispatch", site=site)
            if timeout > 0:
                return _run_with_watchdog(site, thunk, timeout)
            return thunk()
        except BaseException as exc:  # noqa: BLE001 - classified below
            kind = classify_device_error(exc)
            verdict = _proto.device_dispatch_decide(kind, attempt, retries)
            stats = PLANE.stats
            if verdict[0] == "retry":
                attempt = verdict[1]
                if stats is not None:
                    stats.on_device_dispatch_retry(site)
                _time.sleep(min(
                    _RETRY_BACKOFF_CAP_S,
                    _RETRY_BACKOFF_BASE_S * (2 ** (attempt - 1)),
                ))
                continue
            if stats is not None:
                stats.on_device_dispatch_failure(site)
            if verdict[0] == "brownout":
                notify_oom(site)
                if isinstance(exc, DeviceOom):
                    raise
                raise DeviceOom(
                    f"device dispatch at {site} hit HBM exhaustion: {exc!r}"
                ) from exc
            raise
