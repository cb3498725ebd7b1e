"""Plan Doctor — static analysis over the captured dataflow plan.

``pw.analyze(...)`` walks the ParseGraph/operator plan WITHOUT executing
it and emits structured diagnostics (severity, node provenance, fix
hint): fusion blame (which expression/UDF/id= broke the NativeBatch
fused chain), exchange safety (future-time emitters forcing negotiated
frontiers, quiesce-guarded multi-input nodes, elidable gather legs),
replay/retraction safety (non-deterministic UDFs feeding exchanged or
persisted columns), and PATHWAY_* knob validation.

The eligibility predicates in ``analysis.eligibility`` are THE predicates
the executor nodes use at construction time — analyzer and engine cannot
drift (the differential-dataflow stance: operator properties must be
decidable from the plan). The same stance applied to concurrency:
``analysis.meshcheck`` exhaustively model-checks the mesh wave/rollback
protocol by driving the SAME transition table
(``parallel/protocol.py``) the runtime executes, and multi-rank
``pw.analyze`` calls report its distributed-safety verdicts.

CLI: ``python -m pathway_tpu.analysis program.py [--json]
[--processes N] [--require-fused]`` and ``--bench`` for the plan
verdicts of the canonical bench pipelines.

Attribute access is lazy: engine/nodes.py imports
``analysis.eligibility`` at module load, so this package __init__ must
not pull the analyzer (which needs engine.nodes) eagerly.
"""

from __future__ import annotations

_ATTRS = {
    "Diagnostic": ("pathway_tpu.analysis.analyzer", "Diagnostic"),
    "PlanReport": ("pathway_tpu.analysis.analyzer", "PlanReport"),
    "analyze": ("pathway_tpu.analysis.analyzer", "analyze"),
    "analyze_scope": ("pathway_tpu.analysis.analyzer", "analyze_scope"),
    "audit_runtime": ("pathway_tpu.analysis.analyzer", "audit_runtime"),
    "NBDecision": ("pathway_tpu.analysis.eligibility", "NBDecision"),
    "NBStrictError": ("pathway_tpu.analysis.eligibility", "NBStrictError"),
    "eligibility": ("pathway_tpu.analysis.eligibility", None),
    "knobs": ("pathway_tpu.analysis.knobs", None),
    "bench": ("pathway_tpu.analysis.bench", None),
    "meshcheck": ("pathway_tpu.analysis.meshcheck", None),
    "MeshCheckConfig": (
        "pathway_tpu.analysis.meshcheck", "MeshCheckConfig",
    ),
    "MeshCheckReport": (
        "pathway_tpu.analysis.meshcheck", "MeshCheckReport",
    ),
    "check_mesh": ("pathway_tpu.analysis.meshcheck", "check"),
    "ServeCheckConfig": (
        "pathway_tpu.analysis.meshcheck", "ServeCheckConfig",
    ),
    "ServeCheckReport": (
        "pathway_tpu.analysis.meshcheck", "ServeCheckReport",
    ),
    "check_serving": ("pathway_tpu.analysis.meshcheck", "check_serving"),
    "KNOBS": ("pathway_tpu.analysis.knobs", "KNOBS"),
    "KnobError": ("pathway_tpu.analysis.knobs", "KnobError"),
    "knob_table_markdown": (
        "pathway_tpu.analysis.knobs", "knob_table_markdown",
    ),
    "validate_environment": (
        "pathway_tpu.analysis.knobs", "validate_environment",
    ),
}

__all__ = sorted(_ATTRS)


def __getattr__(name: str):
    import importlib

    try:
        mod_name, attr = _ATTRS[name]
    except KeyError:
        raise AttributeError(
            f"module 'pathway_tpu.analysis' has no attribute {name!r}"
        ) from None
    mod = importlib.import_module(mod_name)
    value = mod if attr is None else getattr(mod, attr)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(list(globals().keys()) + list(_ATTRS.keys())))
