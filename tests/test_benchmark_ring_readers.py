"""The benchmark's readers of the span ring (benchmark/layer_metrics/,
benchmark/ring_reduce.py), run with the tier-1 tests too: the file under
benchmark/tests/ holds them."""

import importlib.util
import os

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "tests", "test_ring_readers.py",
)
_spec = importlib.util.spec_from_file_location("benchmark_test_ring_readers", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

ring = _mod.ring
globals().update(
    {name: obj for name, obj in vars(_mod).items() if name.startswith("test_")}
)
