"""pathway_tpu — a TPU-native incremental streaming dataflow framework.

Brand-new implementation of the capabilities of Pathway
(github.com/pathwaycom/pathway, reference mounted at /root/reference):
declarative Table DSL, unified batch+streaming semantics with retractions,
IO connectors, temporal operators, vector indexes and an LLM/RAG xpack —
with the dense hot path (embedders, KNN scoring, rerankers) running on TPU
via JAX/XLA and sharded over device meshes.

Use as: ``import pathway_tpu as pw``.
"""

from __future__ import annotations

from pathway_tpu.internals import reducers
from pathway_tpu.internals.api import (
    ERROR,
    PENDING,
    Json,
    Pointer,
    PyObjectWrapper,
    unsafe_make_pointer,
    wrap_py_object,
)
from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
    apply,
    apply_async,
    apply_with_type,
    assert_table_has_columns,
    cast,
    coalesce,
    declare_type,
    fill_error,
    if_else,
    make_tuple,
    require,
    unwrap,
)
from pathway_tpu.internals.groupbys import GroupedTable
from pathway_tpu.internals.iterate import iterate
from pathway_tpu.internals.joins import JoinMode, JoinResult
from pathway_tpu.internals.parse_graph import G, ParseGraph
from pathway_tpu.internals.schema import (
    ColumnDefinition,
    Schema,
    column_definition,
    schema_builder,
    schema_from_dict,
    schema_from_pandas,
    schema_from_types,
)
from pathway_tpu.internals.table import Table, TableLike
from pathway_tpu.internals.thisclass import left, right, this
from pathway_tpu.internals.universe import SOLVER, Universe
from pathway_tpu.run import run, run_all
from pathway_tpu.udfs import UDF, udf

# user-facing datetime classes (reference: internals/datetime_types.py) —
# usable as schema annotations AND constructors (pw.Duration(days=1));
# the dtype resolver maps them onto DATE_TIME_NAIVE/UTC/DURATION
from pathway_tpu.internals.datetime_types import (  # noqa: E402
    DateTimeNaive,
    DateTimeUtc,
    Duration,
)

from pathway_tpu import debug, io, udfs  # noqa: E402
from pathway_tpu.internals.config import (  # noqa: E402
    PathwayConfig,
    get_pathway_config,
    set_license_key,
    set_monitoring_config,
)
from pathway_tpu.internals.monitoring import MonitoringLevel  # noqa: E402
from pathway_tpu.internals.yaml_loader import load_yaml  # noqa: E402
from pathway_tpu.internals.compat import (  # noqa: E402
    BaseCustomAccumulator,
    PersistenceMode,
    SchemaProperties,
    Type,
    assert_table_has_schema,
    groupby,
    iterate_universe,
    join,
    join_inner,
    join_left,
    join_outer,
    join_right,
    local_error_log,
    schema_from_csv,
    table_transformer,
)
from pathway_tpu.internals.error_log import (  # noqa: E402
    global_error_log,
    remove_errors_from_table,
)
from pathway_tpu.internals.interactive import (  # noqa: E402
    enable_interactive_mode,
    live,
)
from pathway_tpu.internals import interactive  # noqa: E402
from pathway_tpu.internals.row_transformer import (  # noqa: E402
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)
from pathway_tpu.sql_module import sql  # noqa: E402
from pathway_tpu.stdlib.utils.async_transformer import AsyncTransformer  # noqa: E402
from pathway_tpu.stdlib.utils.pandas_transformer import pandas_transformer  # noqa: E402
from pathway_tpu import udfs as asynchronous  # noqa: E402  (reference alias)
from pathway_tpu.internals.interactive import LiveTableHandle as LiveTable  # noqa: E402

# UDF aliases (reference: udf_async/UDFAsync/UDFSync deprecated spellings)
UDFSync = UDF
UDFAsync = UDF


def udf_async(fun=None, **kwargs):
    """reference: pw.udf_async — async-executor UDF decorator."""
    from pathway_tpu.udfs import AsyncExecutor, udf as _udf

    kwargs.setdefault("executor", AsyncExecutor())
    return _udf(fun, **kwargs) if fun is not None else _udf(**kwargs)

__version__ = "0.1.0"

_LAZY_ATTRS = {
    # plan doctor (static dataflow-plan analysis)
    "analyze": ("pathway_tpu.analysis.analyzer", "analyze"),
    "PlanReport": ("pathway_tpu.analysis.analyzer", "PlanReport"),
    # join-result classes exposed at top level (reference __all__)
    "IntervalJoinResult": ("pathway_tpu.stdlib.temporal", "IntervalJoinResult"),
    "AsofJoinResult": ("pathway_tpu.stdlib.temporal", "AsofJoinResult"),
    "WindowJoinResult": (
        "pathway_tpu.stdlib.temporal._window_join", "WindowJoinResult",
    ),
    "Joinable": ("pathway_tpu.internals.table", "Table"),
    "OuterJoinResult": ("pathway_tpu.internals.joins", "JoinResult"),
    "GroupedJoinResult": ("pathway_tpu.internals.groupbys", "GroupedTable"),
    "TableSlice": ("pathway_tpu.internals.table", "_TableSlice"),
    "viz": ("pathway_tpu.stdlib.viz", None),
    "window": ("pathway_tpu.stdlib.temporal", None),
}

_LAZY_MODULES = {
    "analysis": "pathway_tpu.analysis",
    "demo": "pathway_tpu.demo",
    "indexing": "pathway_tpu.stdlib.indexing",
    "temporal": "pathway_tpu.stdlib.temporal",
    "ml": "pathway_tpu.stdlib.ml",
    "stateful": "pathway_tpu.stdlib.stateful",
    "statistical": "pathway_tpu.stdlib.statistical",
    "ordered": "pathway_tpu.stdlib.ordered",
    "graphs": "pathway_tpu.stdlib.graphs",
    "utils": "pathway_tpu.stdlib.utils",
    "xpacks": "pathway_tpu.xpacks",
    "universes": "pathway_tpu.universes",
    "persistence": "pathway_tpu.persistence",
    "sql_module": "pathway_tpu.sql_module",
}


def __getattr__(name: str):
    import importlib

    if name in _LAZY_MODULES:
        mod = importlib.import_module(_LAZY_MODULES[name])
        globals()[name] = mod
        return mod
    if name in _LAZY_ATTRS:
        mod_name, attr = _LAZY_ATTRS[name]
        mod = importlib.import_module(mod_name)
        value = mod if attr is None else getattr(mod, attr)
        globals()[name] = value
        return value
    if name == "sql":
        from pathway_tpu.sql_module import sql as _sql

        globals()["sql"] = _sql
        return _sql
    if name == "iterate":
        from pathway_tpu.internals.iterate import iterate as _iterate

        globals()["iterate"] = _iterate
        return _iterate
    raise AttributeError(f"module 'pathway_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals().keys()) + list(_LAZY_MODULES.keys()) + ["sql", "iterate"])
