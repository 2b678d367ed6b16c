"""Online serving: micro-batched low-latency GLM / GAME scoring.

The reference deploys GAME models behind LinkedIn's online scorers (the
per-entity random-effect story of SURVEY.md §0 only pays off when a
request for user *u* can fetch w_u in microseconds); this package is the
TPU-native analogue of that request path over the batch stack:

- :mod:`~photon_ml_tpu.serving.kernels` — the ONE implementation of
  fixed-effect matvec + random-effect gather + offset sum, shared by
  batch scoring (``GameTransformer`` / ``game_scoring_driver``) and the
  online runtime.
- :mod:`~photon_ml_tpu.serving.runtime` — ``ScoringRuntime``: pre-compiled
  jit kernels at a ladder of padded batch-size buckets, a per-entity
  coefficient table with an LRU hot set resident on device, host-side
  fallback gathers for the cold tail.
- :mod:`~photon_ml_tpu.serving.batcher` — ``MicroBatcher``: bounded-queue
  request coalescing under ``max_batch_size`` / ``max_wait_us``, padding
  to the nearest bucket, per-request futures, admission control and
  deadline timeouts classified through ``utils/watchdog``.
- :mod:`~photon_ml_tpu.serving.service` — ``ScoringService`` (in-process
  API) and a stdlib ``ThreadingHTTPServer`` JSON endpoint (``/score``,
  ``/reload``, ``/healthz``, ``/livez``, ``/readyz``, ``/stats``).
- :mod:`~photon_ml_tpu.serving.supervisor` — ``ReplicaSupervisor``: N
  replicas behind one listener, health probes, request resubmission,
  decorrelated-jitter restarts (the HA story; docs/serving.md).
- :mod:`~photon_ml_tpu.serving.swap` — ``HotSwapper``: zero-downtime
  model hot-swap with verified one-step rollback.
- :mod:`~photon_ml_tpu.serving.loadgen` — closed/open-loop load
  generators plus scripted scenarios (diurnal ramp, skew shift,
  swap-under-load, replica-kill, worker-kill, noisy-neighbor).
- :mod:`~photon_ml_tpu.serving.tenancy` — multi-tenant isolation:
  ``TenantSpec`` / ``TenancyConfig`` (per-tenant bulkhead partitions,
  token-bucket quotas, tiered-admission watermarks, p99 SLOs, circuit
  breakers, enforced in the batcher) and ``TenantRouter`` (tenant ->
  model version on the HotSwapper registry, per-tenant hot swap and
  rollback; docs/serving.md "Tenancy").
- :mod:`~photon_ml_tpu.serving.fleet` — the node tier: ``FleetRouter``
  routes requests across N host endpoints (health probes, DOWN-marking,
  peer resubmission, jittered reconnects, connection draining) and
  ``QuotaCoordinator`` / ``LeaseClient`` carve each tenant's FLEET
  budget into short-lived per-host rate leases (demand-aware
  rebalancing, reclaim on host death, degrade-to-last-lease under
  partition; docs/serving.md "Fleet").
- :mod:`~photon_ml_tpu.serving.procpool` /
  :mod:`~photon_ml_tpu.serving.worker` /
  :mod:`~photon_ml_tpu.serving.shm_model` — crash-isolated worker
  PROCESSES behind the same supervisor seams: the model published once
  into POSIX shared memory with verified (sha256) attach, framed
  request/heartbeat protocol, cross-process hot swap
  (``--workers N``; docs/serving.md "Process mode").
- :mod:`~photon_ml_tpu.serving.wire` — the binary data plane: fixed-
  layout, versioned frames of dtype-tagged columns carrying requests,
  responses, and worker-IPC messages with zero-copy decode and bitwise
  score parity against the JSON path (docs/serving.md "Data plane").
- :mod:`~photon_ml_tpu.serving.shm_ingress` — same-machine ingress: a
  shared-memory slot ring carrying wire frames, skipping HTTP entirely
  for co-located clients (``--shm-ingress``).

``python -m photon_ml_tpu.serving --selfcheck`` builds a synthetic GAME
model, serves concurrent HTTP requests, and verifies batched results are
bit-identical to single-request scoring.  See docs/serving.md.

Imports here are lazy: ``game.estimator`` imports ``serving.kernels``
(the shared scoring math), so the package root must not import modules
that import the estimator back.
"""

from __future__ import annotations

_LAZY = {
    "ScoringRuntime": ("photon_ml_tpu.serving.runtime", "ScoringRuntime"),
    "RuntimeConfig": ("photon_ml_tpu.serving.runtime", "RuntimeConfig"),
    "MicroBatcher": ("photon_ml_tpu.serving.batcher", "MicroBatcher"),
    "BatcherConfig": ("photon_ml_tpu.serving.batcher", "BatcherConfig"),
    "RejectedError": ("photon_ml_tpu.serving.batcher", "RejectedError"),
    "DeadlineExceededError": (
        "photon_ml_tpu.serving.batcher", "DeadlineExceededError",
    ),
    "ScoringService": ("photon_ml_tpu.serving.service", "ScoringService"),
    "start_http_server": (
        "photon_ml_tpu.serving.service", "start_http_server",
    ),
    "ReplicaSupervisor": (
        "photon_ml_tpu.serving.supervisor", "ReplicaSupervisor",
    ),
    "WorkerPool": ("photon_ml_tpu.serving.procpool", "WorkerPool"),
    "ProcessReplica": ("photon_ml_tpu.serving.procpool", "ProcessReplica"),
    "ModelMapError": ("photon_ml_tpu.serving.shm_model", "ModelMapError"),
    "TenancyConfig": ("photon_ml_tpu.serving.tenancy", "TenancyConfig"),
    "TenantSpec": ("photon_ml_tpu.serving.tenancy", "TenantSpec"),
    "TenantRouter": ("photon_ml_tpu.serving.tenancy", "TenantRouter"),
    "FleetRouter": ("photon_ml_tpu.serving.fleet", "FleetRouter"),
    "FleetBudget": ("photon_ml_tpu.serving.fleet", "FleetBudget"),
    "QuotaCoordinator": (
        "photon_ml_tpu.serving.fleet", "QuotaCoordinator",
    ),
    "LeaseClient": ("photon_ml_tpu.serving.fleet", "LeaseClient"),
    "LocalHost": ("photon_ml_tpu.serving.fleet", "LocalHost"),
    "HotSwapper": ("photon_ml_tpu.serving.swap", "HotSwapper"),
    "SwapResult": ("photon_ml_tpu.serving.swap", "SwapResult"),
    "SwapInProgressError": (
        "photon_ml_tpu.serving.swap", "SwapInProgressError",
    ),
    "WireFormatError": ("photon_ml_tpu.serving.wire", "WireFormatError"),
    "ShmIngress": ("photon_ml_tpu.serving.shm_ingress", "ShmIngress"),
    "ShmIngressClient": (
        "photon_ml_tpu.serving.shm_ingress", "ShmIngressClient",
    ),
    "ShmIngressError": (
        "photon_ml_tpu.serving.shm_ingress", "ShmIngressError",
    ),
    "HttpSubmitter": ("photon_ml_tpu.serving.loadgen", "HttpSubmitter"),
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(entry[0]), entry[1])
