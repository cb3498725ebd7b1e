#!/usr/bin/env bash
# Parallel CI lanes (reference pattern: the whole Python suite re-runs
# under PATHWAY_THREADS=n and with real multi-process forks —
# python/pathway/tests/utils.py:31-48,599-677).
#
#   lane 1: full suite with PATHWAY_THREADS=4 (native executor shards)
#   lane 2: full semantics battery with PATHWAY_LANE_PROCESSES=2 —
#           every GraphRunner run transparently joins 2 emulated ranks
#           over the real loopback TCP mesh (lockstep exchanges, gather
#           outputs), re-shaking the batteries for sharding bugs.
#
# Lane-2 deselects: ONLY suites that fork REAL rank processes (their
# children would inherit the lane var on top of real PATHWAY_PROCESSES).
# Serving tests (rest/rag servers, sharded vector store, templates) run
# IN the lane since round 4 — subjects read on rank 0 only, so each
# webserver binds once (VERDICT r4 #4). Deselect-exempt: the columnar
# exchange smoke (test_native_exchange.py::test_exchange_smoke_2rank)
# re-runs AFTER the lane with the lane var cleared, so lane 2 still
# covers one real 2-process mesh end-to-end.
set -e
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# lanes 1/2 run the tier-1 surface (-m 'not slow'); the slow-marked
# mesh grid is covered by lane 3's supervisor smoke and the full
# `python scripts/fault_matrix.py --mesh --mesh-no-nb` sweep
echo "=== lane 0: native GIL-audit + race-audit lint (scripts/lint_gil.py) ==="
# static contract scan over the native batteries (exec.cpp, bm25.cpp,
# hnsw.cpp, fastpath.c): no Python C-API/refcount calls in GIL-released
# regions, Fallback-only failures in phase-1 sections, and the
# shared-state race audit over std::thread worker lambdas (writes must
# be shard-local/atomic/annotated — the static half of lane 6's TSan)
python scripts/lint_gil.py

echo "=== lane 1: PATHWAY_THREADS=4 (full suite) ==="
PATHWAY_THREADS=4 python -m pytest tests/ -x -q -m 'not slow'

echo "=== lane 2: PATHWAY_LANE_PROCESSES=2 (full suite incl. serving) ==="
PATHWAY_LANE_PROCESSES=2 python -m pytest -x -q -m 'not slow' \
  --ignore=tests/test_multiprocess.py \
  --ignore=tests/test_persistence_multiprocess.py \
  --ignore=tests/test_parallel.py \
  --ignore=tests/test_native_exchange.py \
  tests/

echo "=== lane 2 exempt: real 2-process columnar exchange smoke ==="
env -u PATHWAY_LANE_PROCESSES python -m pytest -x -q \
  tests/test_native_exchange.py::test_exchange_smoke_2rank

echo "=== lane 3: real-fork 2-rank mesh kill-and-resume smoke ==="
# one supervised run: a rank-scoped fault plan kills rank 1 mid-wave,
# the survivor detects + aborts the epoch, the supervisor rolls the mesh
# back to the last committed snapshot, output stays bit-identical
env -u PATHWAY_LANE_PROCESSES python -m pytest -x -q \
  tests/test_fault_injection.py::test_mesh_supervisor_kill_and_resume_smoke

echo "=== lane 4: ASan/UBSan native join/exchange batteries ==="
# rebuilds exec.cpp with -fsanitize=address,undefined into a scratch
# build dir and re-runs the join/exchange batteries under it; the script
# self-skips (exit 0 with a message) when g++ lacks sanitizer support
env -u PATHWAY_LANE_PROCESSES ./scripts/sanitize_native.sh asan

echo "=== lane 5: serving gateway smoke (batching + zero drops) ==="
# starts the batching RAG gateway over a mock index and drives
# concurrent keep-alive clients: batch occupancy must exceed 1 (request
# coalescing engaged) and every response must come back correct
env -u PATHWAY_LANE_PROCESSES python scripts/serve_smoke.py

echo "=== lane 6: ThreadSanitizer native battery ==="
# rebuilds the native batteries with -fsanitize=thread and re-runs the
# threaded executor suites under it: the dynamic half of lane 0's race
# audit (the lint names the shard-local write discipline, TSan checks
# the actual schedules). Self-skips when g++ lacks TSan support, like
# lane 4.
env -u PATHWAY_LANE_PROCESSES ./scripts/sanitize_native.sh tsan

echo "=== lane 7: flight-recorder trace smoke (2-rank merge + profile) ==="
# real-fork 2-rank wordcount under PATHWAY_TRACE: both ranks dump
# partials, rank 0 merges ONE Perfetto-loadable trace (per-rank tracks,
# wave/mesh events, epoch marks), the merged JSON validates against the
# trace schema, and the hot-path blame pass (`analysis --profile`)
# exits 0 naming the top self-time node with its fused/degraded verdict
env -u PATHWAY_LANE_PROCESSES python scripts/trace_smoke.py

echo "=== lane 8: serve-through-rollback chaos smoke (kill under load) ==="
# real-fork 2-rank mesh behind the epoch-survivable serving frontend,
# driven by concurrent keep-alive clients with Retry-After retries:
# rank 1 is hard-killed mid-wave (= mid-window-dispatch) under live
# load, and the cell asserts ZERO dropped connections (every admitted
# request gets a terminal response), the frontend's exactly-once
# conservation law, an observed rollback with parked-request replays
# into epoch+1, and records the recovery-window p99. The full grid
# (kill phase × victim × {park-replay, brownout}) runs via
# `python scripts/fault_matrix.py --serve`; the serving park/replay
# protocol itself is model-checked by `python -m pathway_tpu.analysis
# --serve` (mutant: --serve-mutant replay_committed_window).
env -u PATHWAY_LANE_PROCESSES python scripts/serve_chaos_smoke.py

echo "=== lane 9: cluster observatory smoke (4-rank + straggler) ==="
# real-fork 4-rank wordcount with ONE mesh.slow-injected straggler
# (rank 2, seeded delay, no crash): the cluster metrics plane must be
# observable LIVE (/metrics/cluster renders all 4 rank labels, the
# mesh_skew_seconds gauge and scaling_efficiency while the mesh runs),
# the merged trace must land, and `analysis --critical-path` must
# attribute the dominant recv-wait to the injected slow rank. The
# deterministic straggler cell itself is also replayable standalone via
# `python scripts/fault_matrix.py --slow`.
env -u PATHWAY_LANE_PROCESSES python scripts/cluster_smoke.py

echo "=== lane 10: elastic-mesh rescale smoke (2->4->2 under load) ==="
# real-fork supervised mesh serving concurrent keep-alive clients while
# a paced wordcount streams under OPERATOR_PERSISTING: the supervisor
# rescales 2->4 then 4->2 via its control file — ZERO dropped
# connections (conservation audit admitted == responses + expired +
# timeouts), /metrics/cluster shows the new world size LIVE
# (cluster_world_size + 4 live rank labels, departed ranks stale="1"),
# the frontend reports both handoffs on the rescale EWMA, and the
# wordcount capture is bit-identical to a fixed-world run (the
# committed stores re-bucketed 2->4->2 with no key lost/duplicated).
# The kill-during-rescale grid: `python scripts/fault_matrix.py
# --rescale`; the transition is model-checked by `python -m
# pathway_tpu.analysis --mesh --rescale` (mutant drop_reshard_shard).
env -u PATHWAY_LANE_PROCESSES python scripts/rescale_smoke.py

echo "=== lane 11: transactional-egress chaos smoke (sink 2PC) ==="
# real-fork 2-rank mesh writing jsonlines + Delta through the epoch-
# aligned two-phase-commit sinks, killed at every sink phase
# (sink.stage / sink.finalize / sink.recover) and once mid-rescale
# (2->3 re-shard restore): victims die 27, survivors detect + exit 28,
# and after a clean resume the COMMITTED output is bit-identical to a
# fault-free baseline (zero lost, zero duplicated rows). The protocol
# is model-checked by `python -m pathway_tpu.analysis --mesh --sink`
# (mutant: finalize_before_marker); the full grid:
# `python scripts/fault_matrix.py --sink`.
env -u PATHWAY_LANE_PROCESSES python scripts/sink_chaos_smoke.py

echo "=== lane 12: fast-wire compression smoke (zlib 2-rank) ==="
# real-fork 2-rank wordcount under PATHWAY_MESH_COMPRESSION=zlib vs
# off: the live /metrics view must show exchange_uncompressed_bytes >
# exchange_compressed_bytes (ratio > 1 on real typed columnar frames),
# the off run must report the two totals EQUAL (honest off — the
# generic fallback path shares the same framing, so a phantom
# compression state is impossible by construction), and both runs'
# outputs must be bit-identical. The codec corruption contract (CRC
# first, then codec errors, never a partial decode) is pinned by the
# wire fuzz battery in tests/test_native_exchange.py; the gather-tree
# topology is model-checked by `python -m pathway_tpu.analysis --mesh
# --processes 4` (mutant: --mesh-mutant drop_relay).
env -u PATHWAY_LANE_PROCESSES python scripts/compress_smoke.py

echo "=== lane 13: columnar lakehouse smoke (2-rank join -> Delta) ==="
# real-fork 2-rank source -> join -> per-rank partitioned Delta, run on
# the default columnar egress AND with PATHWAY_NO_NB_CAPTURE=1 forcing
# the row path: the columnar run must show capture_arrow_batches_total
# > 0 on every rank's LIVE /metrics (via the cluster view) with ZERO
# rows expanded, nb_fallbacks_total must be flat across the two runs
# (the egress knob moves nothing upstream), and the committed lake
# contents must be bit-identical. The rows-vs-arrow parity battery for
# every sink/workload/rank combination is tests/test_columnar_egress.py
# (runs in lanes 1/2); the export region's GIL discipline is lane 0.
env -u PATHWAY_LANE_PROCESSES python scripts/lakehouse_smoke.py

echo "=== lane 14: device-trace smoke (embed+KNN device plane) ==="
# real-fork embed+KNN pipeline (tiny SentenceEncoder forward in a
# rowwise UDF -> BruteForceKnn ExternalIndexNode) under PATHWAY_TRACE
# with the metrics server on: the LIVE /metrics must show nonzero
# device_dispatch_seconds_total plus the device_mfu /
# device_hbm_peak_bytes gauges, the trace must carry device tracks
# (dispatch-id'd spans correlated to their enclosing node spans), and
# `analysis --profile` must exit 0 naming the top dispatch site with
# its roofline verdict (compute-bound / bandwidth-bound / host-bound).
# The armed-vs-disarmed overhead bar (<= 3%, interleaved pairs) is
# re-measured with `--bench`.
env -u PATHWAY_LANE_PROCESSES python scripts/device_trace_smoke.py

echo "=== lane 15: sharded-index smoke (pod-sharded HBM KNN + encode/add burst) ==="
# real-fork embed+KNN pipeline whose index adapter is backed by the
# pod-sharded index (PATHWAY_INDEX_SHARDS=8 over the emulated 8-device
# CPU mesh) with a burst of SentenceEncoder.encode + KnnShard.add in the
# same traced process: LIVE /metrics must show per-site device samples
# for knn.sharded_search / knn.sharded_write (dispatches + the
# effective-FLOPs family) with ZERO nb_fallbacks_total, the trace must
# carry device spans for the sharded sites, encoder.forward and
# knn.write, and `analysis --profile` must exit 0 naming
# encoder.forward with a roofline verdict. Then in-process: capacity
# scales 4x one chip's slots over 8 shards with zero per-shard growth
# and no empty shard, and sharded-vs-single query p50 is measured
# (flat-within-20% gates real multi-device backends; the CPU emulation
# records the ratio, gross gate only).
# Bit-identical parity is tests/test_sharded_parity.py (lanes 1/2).
env -u PATHWAY_LANE_PROCESSES python scripts/sharded_index_smoke.py

echo "=== lane 16: device fault-domain chaos smoke (snapshot/restore/reshard) ==="
# real-fork embed+KNN index under epoch-aligned HBM snapshots, killed
# mid-cut (device.snapshot cut/post_segment) and mid-recovery
# (device.restore), plus a raise cell absorbed by the dispatch
# supervision: victims die 27, a clean resume restores the committed
# segment chain (NOT re-embedding) and answers bit-identically to a
# fault-free twin with ZERO lost/duplicated entries; the 2->3 rescale
# cell re-buckets through the shard mint; the timing cell pins the
# restore >= 10x faster-than-rebuild bar. The full grid (kill/raise x
# victim x {single-chip, sharded} x {rollback, rescale}) runs via
# `python scripts/fault_matrix.py --device`; the cut/restore/dispatch
# transitions are identity-pinned in tests/test_device_faults.py.
env -u PATHWAY_LANE_PROCESSES python scripts/device_chaos_smoke.py --quick

echo "=== lane 17: backpressure smoke (bounded-memory firehose + pacing) ==="
# real-fork 2-rank firehose under PATHWAY_MEM_BUDGET_MB governance with
# a mesh.slow-throttled sink rank: every rank's peak RSS stays under
# the budget and the ACCOUNTED peak parks in the watermark band (a
# fraction of the bytes the firehose produced — backlog paces, it
# never buffers); output is bit-identical exactly-once vs an
# unthrottled ungoverned baseline with zero drops and zero
# at-least-once degradations on the pausable source; and the pacing
# engage/release cycle is observed LIVE on /metrics/cluster
# (mem_pressure_state leaves ok, connector_paused raises then clears
# with a closed connector_paused_seconds_total episode). The
# pause/drain protocol is model-checked by `python -m
# pathway_tpu.analysis --pace` (mutant: `--pace-mutant never_resume`,
# whose trace replays via `fault_matrix.py --from-trace`), and the
# crash/raise/budget grid runs via `python scripts/fault_matrix.py
# --pressure`; the ladder transitions are identity-pinned in
# tests/test_backpressure.py.
env -u PATHWAY_LANE_PROCESSES python scripts/backpressure_smoke.py

echo "=== lane 18: device doctor (static dispatch-plane analysis) ==="
# zero-execution lowering of every registered device chain (KNN
# scan/write, sharded search/write, encoder forward):
# donation aliasing, host syncs, retrace buckets, HBM budget and mesh
# layout must all verify device-clean on the shipped chains (exit 0
# under --require-device-clean), and each seeded defect class must be
# caught statically with exit 2: an un-donated index write, a mid-chain
# .item() host sync, an unbounded shape-bucket pipeline, and an
# over-budget shard layout. The predicted shape buckets/recompiles are
# pinned against runtime device_recompiles_total in
# tests/test_plan_vs_runtime.py (zero false "clean").
env -u PATHWAY_LANE_PROCESSES python -m pathway_tpu.analysis \
  --device-plan --require-device-clean
for mutant in undonated_write host_sync unbounded_buckets over_budget; do
  if env -u PATHWAY_LANE_PROCESSES python -m pathway_tpu.analysis \
      --device-plan --device-mutant "$mutant" >/dev/null 2>&1; then
    echo "device doctor FAILED to catch seeded mutant: $mutant" >&2
    exit 1
  fi
done

echo "=== all lanes green ==="
