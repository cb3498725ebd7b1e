"""Device Doctor — static dispatch-plane analysis (ISSUE 20).

Plan Doctor pass 6: for every registered device site reachable from the
lowered plan (``internals/device.py`` site registry — encoder forward,
KNN scan/write, sharded search/write), the chain is lowered with
``jax.eval_shape`` / jaxpr inspection under the declared knob/mesh
config — **zero execution, no accelerator needed** — and five checks
emit provenance-carrying diagnostics:

1. **donation audit** — inputs declared donated must appear in the
   lowered input-output aliasing (``tf.aliasing_output`` on the MLIR
   main signature); a donatable index buffer that is NOT donated
   is blamed with the per-dispatch HBM copy cost it silently pays.
2. **host-sync audit** — device→host transfers inside the steady chain:
   blocking callbacks in the jaxpr (``pure_callback``/``io_callback``),
   or ``.item()`` / implicit ``np.asarray`` that abort tracing — the
   static cause of the observatory's host-bound verdicts. The
   diagnostic names the offending eqn/exception and the fix.
3. **retrace audit** — enumerate the shape-bucket set the declared
   workload implies through the SAME bucket functions the dispatch
   sites pad with (``internals/device.py`` — identity-pinned by tests),
   flag unbounded or excessive sets, and predict
   ``device_site_recompiles_total`` per site.
4. **static HBM budget** — per-chip footprint (index shards +
   free-lists + encoder params + snapshot staging) from shapes/dtypes
   and the mesh layout, vs ``device_hbm_bytes()``
   (``PATHWAY_DEVICE_HBM_BYTES`` override for CPU/CI) — a layout that
   cannot hold the declared corpus is refused before PR 17's runtime OOM
   path ever fires.
5. **mesh-layout check** — shard count vs world vs the pow2 tree-merge
   requirement, and ``out_shardings`` pinned on donated sharded writes.

Like eligibility.py, the predicates the checks gate on are the same
objects the runtime sites consume: ``_write_slots``/``_search_fn``
(ops/knn.py), ``make_sharded_write``/``_sharded_search_fn``
(parallel/sharded_knn.py), the encoder's module (models/encoder.py)
and the shared bucket/cost models in ``internals/device.py``.
``join_profile`` joins measured recompiles/MFU from a ``--profile``
trace onto the static predictions with a predicted-vs-measured drift
verdict.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any

from pathway_tpu.analysis.analyzer import SEVERITIES, Diagnostic

MUTANTS = ("undonated_write", "host_sync", "unbounded_buckets", "over_budget")

_CALLBACK_PRIMS = (
    "pure_callback", "io_callback", "debug_callback", "callback",
)


def _max_buckets() -> int:
    raw = os.environ.get("PATHWAY_DEVICE_PLAN_MAX_BUCKETS", "")
    try:
        v = int(raw) if raw.strip() else 64
    except ValueError:
        v = 64
    return max(1, v)


# -- declared workload -------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """The declared steady-state workload the retrace/HBM checks analyze
    under. ``ingest_batches`` are (rows, longest row's tokens) per
    ``SentenceEncoder.encode`` call, whose rows are then written to the
    index in one ``add``; ``write_batches`` are the row counts of index
    writes that follow them with vectors made elsewhere; queries arrive
    in ``query_batches`` sizes asking ``ks`` neighbors.
    ``bounded=False`` declares the batch/shape distribution unbounded —
    exactly the retrace-storm defect the audit refuses."""

    ingest_batches: tuple = ((64, 40), (64, 72), (32, 40))
    write_batches: tuple = (64, 64)
    query_batches: tuple = (1, 8)
    ks: tuple = (10,)
    corpus_rows: int = 4096
    batch_cap: int = 256          # encoder batch_size (pow2 bucket cap)
    initial_capacity: int = 128
    chunk: int | None = None
    bounded: bool = True

    def write_rows(self) -> tuple:
        """Row count of every index write, in order."""
        return tuple(n for n, _ in self.ingest_batches) + tuple(
            self.write_batches
        )


# -- report ------------------------------------------------------------------


@dataclasses.dataclass
class DevicePlanReport:
    """Structured result of one Device Doctor run."""

    verdict: str                  # "device-clean"|"device-degraded"|"device-dirty"
    world: int
    chains: dict = dataclasses.field(default_factory=dict)
    predictions: dict = dataclasses.field(default_factory=dict)
    hbm: dict = dataclasses.field(default_factory=dict)
    diagnostics: list = dataclasses.field(default_factory=list)

    @property
    def device_clean(self) -> bool:
        return self.verdict == "device-clean"

    def errors(self) -> list:
        return [d for d in self.diagnostics if d.severity == "error"]

    def to_dict(self) -> dict:
        counts = {s: 0 for s in SEVERITIES}
        for d in self.diagnostics:
            counts[d.severity] += 1
        return {
            "schema": "pathway_tpu.analysis.device/v1",
            "verdict": self.verdict,
            "world": self.world,
            "chains": self.chains,
            "predictions": {
                site: {
                    "buckets": sorted(map(list, p["buckets"])),
                    "recompiles": p["recompiles"],
                    **({"measured_recompiles": p["measured_recompiles"],
                        "drift": p["drift"]}
                       if "drift" in p else {}),
                }
                for site, p in self.predictions.items()
            },
            "hbm": self.hbm,
            "summary": {"diagnostics": counts},
            "diagnostics": [d.to_dict() for d in self.diagnostics],
        }

    def to_json(self, **kwargs) -> str:
        kwargs.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **kwargs)

    def render(self) -> str:
        lines = [
            f"device plan verdict: {self.verdict.upper()} "
            f"(world={self.world})"
        ]
        for name, verdict in sorted(self.chains.items()):
            mark = {"clean": "+", "degraded": "!", "dirty": "-"}.get(
                verdict, "?"
            )
            lines.append(f"  [{mark}] chain {name:<10} {verdict}")
        for site, p in sorted(self.predictions.items()):
            drift = (
                f"  measured={p['measured_recompiles']} drift={p['drift']}"
                if "drift" in p else ""
            )
            lines.append(
                f"  site {site:<20} buckets={len(p['buckets'])} "
                f"predicted_recompiles={p['recompiles']}{drift}"
            )
        if self.hbm:
            lines.append(
                f"  hbm: footprint={self.hbm.get('footprint_bytes', 0):.3e} "
                f"budget={self.hbm.get('budget_bytes', 0):.3e} "
                f"({self.hbm.get('share', 0.0):.1%} of one chip)"
            )
        for d in self.diagnostics:
            lines.append(d.render())
        return "\n".join(lines)


# -- lowering helpers (zero execution) ---------------------------------------


def _main_signature(mlir_text: str) -> str:
    """The argument list of the lowered module's @main — paren-matched
    so multi-line signatures and nested loc(...) annotations survive."""
    at = mlir_text.find("@main(")
    if at < 0:
        return ""
    i = at + len("@main(")
    depth = 1
    j = i
    while j < len(mlir_text) and depth:
        c = mlir_text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        j += 1
    return mlir_text[i:j - 1]


def _aliased_flat_args(mlir_text: str) -> set[int]:
    """Flat input indices carrying the donation marker: jax's lowering
    stamps ``tf.aliasing_output`` on every input the compiled executable
    aliases to an output (verified on the pinned jax: the attribute IS
    the aliasing contract, there is no separate buffer-donor marker)."""
    sig = _main_signature(mlir_text)
    out: set[int] = set()
    for m in re.finditer(r"%arg(\d+)((?:(?!%arg\d+).)*)", sig, re.S):
        if "tf.aliasing_output" in m.group(2):
            out.add(int(m.group(1)))
    return out


def _donated_flat_indices(avals: tuple, donate_argnums: tuple) -> list[int]:
    """Map python-arg donation numbers to flat (leaf) input positions —
    a pytree arg (the params dict) flattens to many avals."""
    import jax

    flat: list[int] = []
    pos = 0
    for i, a in enumerate(avals):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate_argnums:
            flat.extend(range(pos, pos + n))
        pos += n
    return flat


def _walk_jaxpr_callbacks(jaxpr) -> list[str]:
    """Recursively collect host-callback primitive names from a (closed)
    jaxpr — each one is a device→host sync inside the steady chain."""
    found: list[str] = []
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if any(name.startswith(p) for p in _CALLBACK_PRIMS):
            found.append(name)
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                found.extend(_walk_jaxpr_callbacks(v))
            elif isinstance(v, (list, tuple)):
                for item in v:
                    if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                        found.extend(_walk_jaxpr_callbacks(item))
    return found


def _host_sync_check(
    fn, avals: tuple, site: str, where: str, diags: list, static_kwargs=None
) -> bool:
    """Trace ``fn`` abstractly and audit for host syncs. Returns True
    when the chain traced clean; a concretization abort or a callback
    eqn emits the diagnostic and returns False."""
    import jax

    try:
        jaxpr = jax.make_jaxpr(
            fn, static_argnums=(), **({} if not static_kwargs else {})
        )(*avals, **(static_kwargs or {}))
    except Exception as exc:
        kind = type(exc).__name__
        if "Concretization" in kind or "TracerArrayConversion" in kind \
                or "TracerBoolConversion" in kind:
            diags.append(Diagnostic(
                code="device.host_sync",
                severity="error",
                node=site,
                message=(
                    f"the steady chain forces a device->host sync while "
                    f"tracing ({kind}): a `.item()` / `float()` / implicit "
                    f"`np.asarray` on a device value blocks the dispatch "
                    f"queue every call — the static cause of a host-bound "
                    f"roofline verdict"
                ),
                hint=(
                    "keep the chain traceable: replace host reads with "
                    "jnp ops / lax.cond, and move scalar extraction "
                    "outside the jitted chain"
                ),
                where=where,
            ))
            return False
        raise
    callbacks = _walk_jaxpr_callbacks(jaxpr)
    if callbacks:
        diags.append(Diagnostic(
            code="device.host_sync",
            severity="error",
            node=site,
            message=(
                f"lowered chain contains blocking host callback eqn(s) "
                f"{sorted(set(callbacks))}: each one round-trips "
                f"device->host inside the steady chain"
            ),
            hint=(
                "drop the callback from the hot chain (pre/post-process "
                "on the host) or make it async outside the dispatch"
            ),
            where=where,
        ))
        return False
    return True


def _donation_check(
    jitfn, avals: tuple, donate_argnums: tuple, donatable_bytes: float,
    site: str, where: str, diags: list, static_kwargs=None,
) -> bool:
    """Lower ``jitfn`` at the avals and verify every declared-donated
    input carries the aliasing marker. Returns True when donation holds;
    a donatable buffer set that is NOT aliased gets blamed with the
    per-dispatch HBM copy cost."""
    lowered = jitfn.lower(*avals, **(static_kwargs or {}))
    text = lowered.as_text()
    aliased = _aliased_flat_args(text)
    wanted = _donated_flat_indices(avals, tuple(donate_argnums))
    missing = [i for i in wanted if i not in aliased]
    if not donate_argnums or missing:
        mb = donatable_bytes / 1e6
        diags.append(Diagnostic(
            code="device.donation",
            severity="error",
            node=site,
            message=(
                "index buffers are donatable but the lowered "
                "executable does not alias them in-place"
                + (f" (flat inputs {missing} lack tf.aliasing_output)"
                   if donate_argnums else
                   " (the jit declares no donate_argnums at all)")
                + f": every dispatch pays a ~{mb:.2f} MB HBM copy of the "
                  "buffer triple and doubles its steady footprint"
            ),
            hint=(
                "jit the chain with donate_argnums covering the buffer "
                "triple (see ops/knn.py _write_slots / "
                "parallel/sharded_knn.py make_sharded_write) and keep "
                "shapes/dtypes of donor and output identical so XLA "
                "can alias"
            ),
            where=where,
        ))
        return False
    return True


# -- retrace audit (shared bucket enumeration) -------------------------------


def simulate_knn_buckets(spec: WorkloadSpec) -> tuple[set, set]:
    """(write, search) compiled-shape sets of the declared workload on a
    single-chip shard — the same growth schedule and k clamps the
    runtime applies (pow2_capacity/knn_write_bucket/knn_search_bucket)."""
    from pathway_tpu.internals.device import (
        knn_search_bucket, knn_write_bucket, pow2_capacity,
    )

    cap = pow2_capacity(spec.initial_capacity)
    rows = 0
    wb: set = set()
    for b in spec.write_rows():
        rows += b
        cap = max(cap, pow2_capacity(rows))
        wb.add(knn_write_bucket(b, cap))
    sb: set = set()
    for q in spec.query_batches:
        for k in spec.ks:
            sb.add(knn_search_bucket(q, cap, k, spec.chunk))
    return wb, sb


def simulate_sharded_buckets(
    spec: WorkloadSpec, world: int
) -> tuple[set, set]:
    """(write, search) compiled-shape sets of the declared workload on a
    ``world``-shard index (local capacity doubles from 128 to hold each
    shard's rows; the merge/k clamps mirror ShardedKnnIndex.search)."""
    from pathway_tpu.internals.device import (
        pow2_capacity, sharded_search_bucket, sharded_write_bucket,
    )

    local = pow2_capacity(max(1, spec.initial_capacity // max(world, 1)))
    rows = 0
    wb: set = set()
    for b in spec.write_rows():
        rows += b
        # evenly-routed model: every shard holds ~rows/world
        local = max(local, pow2_capacity(-(-rows // max(world, 1))))
        wb.add(sharded_write_bucket(b, world * local))
    sb: set = set()
    for q in spec.query_batches:
        for k in spec.ks:
            sb.add(sharded_search_bucket(q, world, local, k, spec.chunk))
    return wb, sb


def _retrace_audit(
    spec: WorkloadSpec, site: str, buckets: set, where: str,
    diags: list, predictions: dict,
) -> None:
    if not spec.bounded:
        diags.append(Diagnostic(
            code="device.retrace.unbounded",
            severity="error",
            node=site,
            message=(
                "the declared workload has no batch/shape bound: every "
                "novel shape is a fresh XLA lower+compile — an unbounded "
                "executable set (retrace storm) and an unbounded "
                "compiled-fn cache"
            ),
            hint=(
                "declare batch/sequence caps so padding buckets the "
                "shape set (encoder pad_batch, pow2 query padding), or "
                "chunk the stream to a fixed batch size upstream"
            ),
            where=where,
        ))
    cap = _max_buckets()
    if len(buckets) > cap:
        diags.append(Diagnostic(
            code="device.retrace.excessive",
            severity="warning",
            node=site,
            message=(
                f"declared workload implies {len(buckets)} compiled "
                f"shape buckets (> PATHWAY_DEVICE_PLAN_MAX_BUCKETS="
                f"{cap}): compile time and executable memory scale with "
                "every bucket"
            ),
            hint="coarsen the bucket schedule or narrow the declared "
                 "batch/length distribution",
            where=where,
        ))
    predictions[site] = {
        "buckets": set(buckets),
        "recompiles": len(buckets),
    }


# -- the doctor --------------------------------------------------------------


def analyze_device_plan(
    *,
    workload: WorkloadSpec | None = None,
    world: int = 1,
    config: Any = None,
    mutant: str | None = None,
    answer: Any = None,
) -> DevicePlanReport:
    """Run the five static checks over every registered device chain at
    the declared ``world``/workload. ``mutant`` seeds one of the four
    defect classes into the chains the product dispatches (tests + the CI
    lane's exit-2 contract): ``undonated_write`` into the index write of
    the declared world (``knn.write`` on one chip, ``knn.sharded_write``
    across several), ``host_sync`` into ``encoder.forward`` and
    ``knn.search``; None analyzes the shipped chains. ``answer``: the
    ``DecoderConfig`` of an answer model resident beside the index; its
    held parameters and its state cache count into the HBM budget. Zero
    execution: chains are lowered with ShapeDtypeStructs — nothing is
    dispatched."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.internals import device as dev
    from pathway_tpu.models.encoder import (
        EncoderConfig,
        TransformerEncoder,
        encoder_param_bytes,
    )
    from pathway_tpu.ops.knn import _search_fn, _write_slots

    if mutant is not None and mutant not in MUTANTS:
        raise ValueError(f"unknown device mutant {mutant!r}; one of {MUTANTS}")
    spec = workload or WorkloadSpec()
    if mutant == "unbounded_buckets":
        spec = dataclasses.replace(spec, bounded=False)
    if mutant == "over_budget":
        # a corpus no single chip can hold at the declared layout
        spec = dataclasses.replace(spec, corpus_rows=2**31)
    cfg = config or EncoderConfig.tiny()
    world = max(1, int(world))
    diags: list[Diagnostic] = []
    predictions: dict = {}
    chains: dict = {}
    S = jax.ShapeDtypeStruct

    def chain_verdict(before: int) -> str:
        new = diags[before:]
        if any(d.severity == "error" for d in new):
            return "dirty"
        if any(d.severity == "warning" for d in new):
            return "degraded"
        return "clean"

    def seeded(fn):
        """``fn`` with the ``host_sync`` defect: a mid-chain scalar read
        that forces a device->host sync on every dispatch."""
        if mutant != "host_sync":
            return fn

        def synced(*args):
            out = fn(*args)
            jax.tree_util.tree_leaves(out)[0].sum().item()
            return out

        return synced

    model = TransformerEncoder(cfg)
    d_model = cfg.hidden
    nb = dev.batch_bucket(
        max((n for n, _ in spec.ingest_batches), default=8), 8, spec.batch_cap
    )
    Lb = dev.seq_bucket(
        max((L for _, L in spec.ingest_batches), default=16), cfg.max_len
    )
    cap0 = dev.pow2_capacity(spec.initial_capacity)
    rng = jax.random.PRNGKey(0)
    # parameter avals WITHOUT initializing real weights: eval_shape on
    # model.init is the zero-execution path
    params_avals = jax.eval_shape(
        model.init, rng,
        S((1, 8), jnp.int32), S((1, 8), jnp.int32),
    )["params"]
    undonated = None
    if mutant == "undonated_write":
        undonated = "knn.write" if world == 1 else "knn.sharded_write"

    def write_chain(site, fn, **jit_kwargs):
        """``fn`` (the site's jitted write) and its donated argnums; the
        seeded defect: the same write jitted without its donation."""
        if site != undonated:
            return fn, (0, 1, 2)
        return jax.jit(
            _write_slots.__wrapped__, static_argnames=("normalize",),
            **jit_kwargs,
        ), ()

    # -- chain: knn.write / knn.search --------------------------------------
    mark = len(diags)
    knn_where = "pathway_tpu/ops/knn.py:KnnShard"
    wb, sb = simulate_knn_buckets(spec)
    write_rows = max(spec.write_rows(), default=64)
    write_avals = (
        S((cap0, d_model), jnp.float32),
        S((cap0,), jnp.bool_),
        S((cap0,), jnp.float32),
        S((write_rows,), jnp.int32),
        S((write_rows, d_model), jnp.float32),
        S((write_rows,), jnp.bool_),
    )
    write_fn, donate = write_chain("knn.write", _write_slots)
    if _host_sync_check(
        _write_slots.__wrapped__, write_avals, "knn.write",
        knn_where + ".add", diags,
    ):
        _donation_check(
            write_fn, write_avals, donate,
            dev.index_shard_bytes(cap0, d_model),
            "knn.write", knn_where + ".add", diags,
        )
    if sb:
        qn, scap, k_eff = max(sb)
        sfn = _search_fn(k_eff, "cos", spec.chunk, "highest")
        search_avals = (
            S((qn, d_model), jnp.float32),
            S((scap, d_model), jnp.float32),
            S((scap,), jnp.bool_),
            S((scap,), jnp.float32),
        )
        _host_sync_check(
            seeded(sfn), search_avals, "knn.search", knn_where + ".search",
            diags,
        )
    _retrace_audit(spec, "knn.write", wb, knn_where + ".add", diags,
                   predictions)
    _retrace_audit(spec, "knn.search", sb, knn_where + ".search", diags,
                   predictions)
    chains["knn"] = chain_verdict(mark)

    # -- chain: sharded write/search + mesh layout --------------------------
    mark = len(diags)
    sh_where = "pathway_tpu/parallel/sharded_knn.py:ShardedKnnIndex"
    swb, ssb = simulate_sharded_buckets(spec, world)
    try:
        import numpy as np
        from jax.sharding import Mesh

        from pathway_tpu.parallel.sharded_knn import (
            _sharded_search_fn,
            make_sharded_write,
        )

        # real lowering happens on a world-1 CPU mesh (CPU has one jax
        # device); the declared-world checks below are pure-model
        mesh1 = Mesh(np.array(jax.devices()[:1]), ("dp",))
        wfn, out_shardings = make_sharded_write(mesh1, "dp")
        wfn, donate = write_chain(
            "knn.sharded_write", wfn, out_shardings=out_shardings
        )
        if _host_sync_check(
            _write_slots.__wrapped__, write_avals, "knn.sharded_write",
            sh_where + ".add", diags,
        ):
            _donation_check(
                wfn, write_avals, donate,
                dev.index_shard_bytes(cap0, d_model),
                "knn.sharded_write", sh_where + ".add", diags,
            )
        if out_shardings is None or len(out_shardings) != 3:
            diags.append(Diagnostic(
                code="device.mesh.out_shardings",
                severity="error",
                node="knn.sharded_write",
                message="donated sharded write without pinned "
                        "out_shardings: the scatter may silently "
                        "replicate the store",
                hint="build the writer through make_sharded_write "
                     "(out_shardings pinned to the shard layout)",
                where=sh_where + ".add",
            ))
        if ssb:
            qn, scap, k_eff = max(ssb)
            ssfn = _sharded_search_fn(
                mesh1, "dp", min(k_eff, cap0), "cos", spec.chunk,
                "highest", "gather",
            )
            s_avals = (
                S((qn, d_model), jnp.float32),
                S((cap0, d_model), jnp.float32),
                S((cap0,), jnp.bool_),
                S((cap0,), jnp.float32),
            )
            _host_sync_check(
                ssfn, s_avals, "knn.sharded_search",
                sh_where + ".search", diags,
            )
    except Exception as exc:  # lowering infrastructure missing, not a defect
        diags.append(Diagnostic(
            code="device.chain.unlowerable",
            severity="warning",
            node="knn.sharded_write",
            message=f"sharded chain could not be lowered statically: "
                    f"{type(exc).__name__}: {exc}",
            hint="run under JAX_PLATFORMS=cpu with jax installed",
            where=sh_where,
        ))
    # declared-world mesh model (pure — no device needed)
    merge_raw = str(
        os.environ.get("PATHWAY_INDEX_MERGE", "auto")
    ).strip().lower()
    pow2 = world & (world - 1) == 0
    if merge_raw == "tree" and not pow2:
        diags.append(Diagnostic(
            code="device.mesh.merge",
            severity="warning",
            node="knn.sharded_search",
            message=(
                f"PATHWAY_INDEX_MERGE=tree requires a pow2 shard axis; "
                f"world={world} silently degrades to gather (per-link "
                f"traffic grows with the pod)"
            ),
            hint="use a pow2 world for the index axis or set "
                 "PATHWAY_INDEX_MERGE=auto",
            where=sh_where + ".search",
        ))
    _retrace_audit(spec, "knn.sharded_write", swb, sh_where + ".add",
                   diags, predictions)
    _retrace_audit(spec, "knn.sharded_search", ssb, sh_where + ".search",
                   diags, predictions)
    chains["sharded"] = chain_verdict(mark)

    # -- chain: encoder.forward ---------------------------------------------
    mark = len(diags)
    enc_where = ("pathway_tpu/models/encoder.py:"
                 "SentenceEncoder.encode_tokens_device")

    def forward(params, ids, mask):
        return model.apply({"params": params}, ids, mask)

    _host_sync_check(
        seeded(forward),
        (params_avals, S((nb, Lb), jnp.int32), S((nb, Lb), jnp.int32)),
        "encoder.forward", enc_where, diags,
    )
    enc_buckets = {
        dev.encoder_bucket(rows, width, cfg.vocab_size <= 65536)
        for n, L in spec.ingest_batches
        for rows, width in dev.encoder_call_shapes(
            n, L, spec.batch_cap, cfg.max_len
        )
    }
    _retrace_audit(spec, "encoder.forward", enc_buckets, enc_where, diags,
                   predictions)
    chains["encoder"] = chain_verdict(mark)

    # -- static HBM budget ---------------------------------------------------
    per_chip_rows = -(-spec.corpus_rows // world)
    per_chip_cap = dev.pow2_capacity(per_chip_rows)
    donation_ok = not any(
        d.code == "device.donation" for d in diags
    )
    index_b = dev.index_shard_bytes(
        per_chip_cap, d_model, donated=donation_ok
    )
    freelist_b = 8.0 * per_chip_cap  # host slot free-list + freed-epoch
    params_b = encoder_param_bytes(cfg)
    snap_b = dev.snapshot_staging_bytes(per_chip_cap, d_model)
    answer_params_b = answer_cache_b = 0.0
    if answer is not None:
        from pathway_tpu.models import decoder

        answer_params_b = decoder.param_bytes(answer)
        answer_cache_b = decoder.cache_bytes(answer)
    footprint = (
        index_b + freelist_b + params_b + snap_b
        + answer_params_b + answer_cache_b
    )
    budget = float(dev.device_hbm_bytes())
    hbm = {
        "world": world,
        "per_chip_capacity": per_chip_cap,
        "index_bytes": index_b,
        "freelist_bytes": freelist_b,
        "encoder_param_bytes": params_b,
        "snapshot_staging_bytes": snap_b,
        "answer_param_bytes": answer_params_b,
        "answer_cache_bytes": answer_cache_b,
        "footprint_bytes": footprint,
        "budget_bytes": budget,
        "share": footprint / budget if budget else 0.0,
        "donated": donation_ok,
    }
    if footprint > budget:
        diags.append(Diagnostic(
            code="device.hbm.over_budget",
            severity="error",
            node="knn.write" if world == 1 else "knn.sharded_write",
            message=(
                f"declared corpus of {spec.corpus_rows} rows needs "
                f"{footprint:.3e} bytes/chip (index {index_b:.3e} + "
                f"params {params_b:.3e} + "
                f"snapshot {snap_b:.3e}) but the device budget is "
                f"{budget:.3e} bytes — this layout OOMs before serving"
            ),
            hint=(
                "shard over more chips (capacity scales with the mesh), "
                "shrink the declared corpus, or raise "
                "PATHWAY_DEVICE_HBM_BYTES if the budget model is wrong "
                "for this hardware"
            ),
            where=knn_where if world == 1 else sh_where,
        ))
        chains["sharded" if world > 1 else "knn"] = "dirty"

    # -- registry coverage ---------------------------------------------------
    for name, site in sorted(dev.registered_sites().items()):
        if not callable(site.cost_model) or not isinstance(
            site.dtypes, tuple
        ):
            diags.append(Diagnostic(
                code="device.registry",
                severity="error",
                node=name,
                message="registered device site lacks a callable cost "
                        "model / dtype tuple (registry drift)",
                hint="register via device_site(name, cost_model=..., "
                     "dtypes=...) next to the dispatch",
                where=site.where or None,
            ))

    if any(d.severity == "error" for d in diags):
        verdict = "device-dirty"
    elif any(d.severity == "warning" for d in diags):
        verdict = "device-degraded"
    else:
        verdict = "device-clean"
    diags.sort(key=lambda d: -SEVERITIES.index(d.severity))
    return DevicePlanReport(
        verdict=verdict, world=world, chains=chains,
        predictions=predictions, hbm=hbm, diagnostics=diags,
    )


# -- predicted vs measured drift (--profile join) ----------------------------


def join_profile(report: DevicePlanReport, trace: dict | str) -> DevicePlanReport:
    """Join measured per-site recompile counters from a flight-recorder
    trace (its ``pathway.device_recompiles`` block) onto the static
    predictions. A site whose measured recompiles exceed the predicted
    bucket count is DRIFT — the static model missed shapes the runtime
    actually compiled; measured <= predicted is ok (a run need not visit
    every declared bucket)."""
    if isinstance(trace, str):
        with open(trace, "r", encoding="utf-8") as fh:
            trace = json.load(fh)
    doc = trace.get("pathway", trace) if isinstance(trace, dict) else {}
    measured = doc.get("device_recompiles") or {}
    for site, p in report.predictions.items():
        if site not in measured:
            continue
        got = int(measured[site])
        p["measured_recompiles"] = got
        p["drift"] = "ok" if got <= p["recompiles"] else "exceeded"
        if p["drift"] == "exceeded":
            report.diagnostics.append(Diagnostic(
                code="device.retrace.drift",
                severity="error",
                node=site,
                message=(
                    f"measured device recompiles ({got}) exceed the "
                    f"static prediction ({p['recompiles']}): the runtime "
                    "compiled shapes the declared workload did not imply"
                ),
                hint="re-declare the workload (batch/length caps) or fix "
                     "the site's bucket schedule",
            ))
            report.verdict = "device-dirty"
    return report
