#!/usr/bin/env bash
# Repo-local check: telemetry selfcheck + the tier-1 test suite.
#
#   scripts/check.sh            # selfcheck + full tier-1 (CPU backend)
#   scripts/check.sh --fast     # selfcheck + the telemetry/watchdog tests
#
# The selfcheck (python -m photon_ml_tpu.telemetry --selfcheck) pushes a
# synthetic span tree through every sink and validates events.jsonl /
# trace.json / metrics.json; it is device-free and takes < 1 s, so run
# it first — a broken sink should fail in seconds, not after the suite.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== telemetry selfcheck =="
python -m photon_ml_tpu.telemetry --selfcheck

# Metric-name lint: every registered metric name in the source tree
# conforms to <subsystem>_<name>_<unit> and no name is used as two
# different kinds (now the analysis/ metric-naming rule; this entry
# point is a thin alias kept for muscle memory).
echo "== telemetry metric-name lint =="
python -m photon_ml_tpu.telemetry --lint-metrics

# Project-wide invariant checker (docs/analysis.md): thread lifecycle /
# lock discipline / wall-clock hygiene, JAX donation + purity, chaos-
# site and metric-name registry sync.  Device-free, AST-only, ~2 s;
# fails on any finding outside the committed baseline.
echo "== analysis invariant check =="
python -m photon_ml_tpu.analysis --check

# The serving selfcheck runs three passes: the single-runtime pass
# builds a synthetic GAME model, serves concurrent HTTP requests, and
# verifies batched results are bit-identical to single-request scoring
# (plus the telemetry snapshot contents); the HA pass kills one of two
# replicas and hot-swaps v1->v2 under live load (plus a tampered-model
# rollback), gating on ZERO failed requests and a monotone
# serving_model_version; the tenancy pass replays the noisy_neighbor
# scenario — an aggressor tenant bursting 10x its quota sheds alone
# while the victim tenant's p99 holds inside its SLO with zero failures.
echo "== serving selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.serving --selfcheck

# The process-mode serving selfcheck runs the same contracts against
# crash-isolated worker PROCESSES on one shared-memory model: score
# parity with in-process scoring, a real SIGKILL under open-loop load
# with zero failed requests, a cross-process hot swap + rollback
# (bit-identical), single-publication segment accounting, and a
# leak-free shutdown under a strict ProcessLeakSentinel — then the
# noisy-neighbor tenancy pass with the tenant id riding the worker
# wire protocol (victim zero-failures gate in process mode too).
echo "== serving process-mode selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.serving --selfcheck --workers 2

# The tuning selfcheck runs a parallel ASHA+GP search on a synthetic
# GAME workload, kills it mid-flight, resumes from tuning_state.jsonl,
# and asserts the resumed trial history + journal decision sequence are
# identical to an uninterrupted run (plus executor crash/retry paths
# and the tuning telemetry contract).
echo "== tuning selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.tuning --selfcheck

# The chaos selfcheck runs the scripted kill/resume/degrade scenario:
# a streamed GLM grid and a GAME CD run killed mid-flight resume
# bitwise-identically through the watchdog, a mid-pass streaming fault
# tears down cleanly, a device-lost fault degrades serving with zero
# request errors and the breaker re-promotes, and checkpoint corruption
# falls back / raises pointed errors (docs/robustness.md).
echo "== chaos selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.chaos --selfcheck

# The freshness selfcheck runs the whole continuous train->serve loop:
# labeled events from a drifted truth model online-refine the serving
# model, the refinement delta-publishes crash-safely and hot-applies to
# a live 2-replica service MID-SCENARIO under open-loop load, gating on
# zero failed requests, bitwise parity with a full reload of the
# refined model, one-step rollback, and the event->servable freshness
# SLO landing in metrics.json (docs/freshness.md).
echo "== freshness selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.freshness --selfcheck

# The cluster selfcheck replays the 3-host control-plane drill under
# open-loop load: the leader quota-coordinator replica is killed and a
# peer takes over within one lease TTL (over-admission bounded to one
# lease window by the journal replay), a cold host bootstraps from the
# newest snapshot publication over HTTP (checksums end to end, scores
# bit-identical) and joins via the membership registry while another
# host drains — zero failed requests throughout (docs/serving.md
# "Cluster").
echo "== cluster selfcheck (JAX_PLATFORMS=cpu) =="
env JAX_PLATFORMS=cpu python -m photon_ml_tpu.cluster --selfcheck

echo "== tier-1 tests (JAX_PLATFORMS=cpu) =="
if [[ "${1:-}" == "--fast" ]]; then
  # Streaming-parity smoke rides the fast lane: a tiny 4-chunk store,
  # asserting the windowed-async pipeline is BIT-IDENTICAL to the
  # depth=1 serial baseline (value/grad, hvp, scores) — the invariant
  # every other streamed number rests on.  The transfer-avoidance smoke
  # repeats the same 4-chunk parity with compressed wire chunks + the
  # hot working-set cache enabled.  test_chaos's kill/resume
  # boundary matrices are the fast recovery smoke.  The fleet smoke is
  # a 2-host router with a scripted host kill under in-flight load:
  # zero failed requests, the killed host rejoins.  test_serving_wire
  # is the binary-parity smoke: a 3-bucket synthetic model scored over
  # live HTTP in both wire formats must produce BITWISE-identical
  # scores (plus fused-kernel parity and frame refusal tests).  The
  # solver smoke pins the solver choice (explicit --solver lbfgs is
  # bitwise the implicit routing) and consensus-ADMM landing within
  # 1e-5 of the resident OWL-QN optimum over logical shards.
  # test_cluster covers the control plane: membership expiry/heal,
  # coordinator leader failover + journal replay, and checksum-verified
  # publication fetch (all three cluster.* chaos seams).  The
  # hierarchical-GAME smoke runs one sharded-vs-single parity leg on
  # the forced multi-device mesh (resident + out-of-core: packed blocks
  # BITWISE, split blocks within 4 ulp) — the invariant the mesh
  # bucket-shard plan rests on.
  exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_telemetry.py tests/test_ops_plane.py \
    tests/test_watchdog.py \
    tests/test_serving.py tests/test_serving_ha.py \
    tests/test_serving_proc.py tests/test_freshness.py \
    tests/test_serving_wire.py \
    tests/test_distributed_tracing.py \
    tests/test_cluster.py \
    tests/test_tuning.py tests/test_chaos.py \
    "tests/test_streaming.py::TestPipelineParity::test_async_window_bit_identical_to_sync_f32" \
    "tests/test_streaming.py::TestTransferAvoidance::test_fast_lane_compressed_cached_parity" \
    "tests/test_serving_fleet.py::TestFleetRouter::test_host_kill_under_load_costs_zero_failures" \
    "tests/test_solvers.py::TestDispatchParity::test_resident_bitwise" \
    "tests/test_solvers.py::TestADMM::test_logical_shards_match_owlqn" \
    "tests/test_game_hierarchical.py::TestShardedParity::test_resident_bitwise[per_user-shape0]" \
    "tests/test_game_hierarchical.py::TestShardedParity::test_out_of_core_bitwise[per_user-shape0]" \
    -m 'not slow' -q -p no:cacheprovider
fi
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider \
  -p no:xdist -p no:randomly
