"""Out-of-core GLM training: stream host chunks through the chip per pass.

The resident solvers (optim/lbfgs.py) run the ENTIRE optimize loop inside
one jitted ``lax.while_loop`` — possible only because the dataset lives in
HBM.  When it does not (BASELINE.json's north-star configs are 1B rows ≈
hundreds of GB of slot data), the structure inverts to the reference's own
shape: the OUTER loop runs on the host (the reference's driver-side Breeze
L-BFGS — SURVEY.md §2 Optimizers), and each objective evaluation is one
full pass over the data (the ``treeAggregate`` analogue, SURVEY.md §3.1) —
here a three-stage software pipeline of host chunks, value/grad
accumulated on device:

    pack thread:     stack/slice chunk k+2's host buffers ──►
    transfer thread: chunk k+1 ──one coalesced transfer──► HBM
    caller thread:   HBM chunk k ──unpack+Pallas/XLA──► (value, grad) +=

Each chunk crosses as a few large dtype-segregated staging buffers
(data/staging.py), the pack and transfer stages run on their own threads
(data/prefetch.py) with ``prefetch_depth`` (default 2) chunks in flight,
and the consumer syncs on a bounded WINDOW of carries (it dispatches
chunk k's program, then waits only for chunk k-depth's carry), so the
device never idles during a chunk's Python dispatch.  Accumulator
buffers are donated back to XLA each step (in-place updates), HBM holds
O(``prefetch_depth``) chunks regardless of dataset size, and the f32
accumulation order stays strictly per-chunk-sequential — the async
pipeline is bit-identical to the ``prefetch_depth=1`` serial baseline
(pinned by tests/test_streaming.py).  ``chunk_fuse > 1`` additionally
stacks that many chunks per dispatch and folds them with an in-program
``lax.scan`` (same order, one dispatch), amortizing per-dispatch
overhead when chunks are small.

The inner per-chunk program is ONE jitted function for all chunks
(uniform shapes — see data/streaming.py) with the staging unpack traced
in, so there is one compile per solve (two with a ragged fused tail);
per-chunk transfer timing, per-stage wall attribution, and stall
counters accumulate on ``StreamingObjective.transfer_stats``.

Host-loop math mirrors lbfgs_solve step-for-step (same two-loop recursion
and history via the SAME jitted helpers, same weak-Wolfe bracketing, same
stall/convergence rules), so a single-chunk streamed solve lands on the
resident solution to float tolerance; tests/test_streaming.py pins that.
Line searches batch their trials: one streamed pass evaluates the current
candidate step PLUS its possible successors (vector-free-L-BFGS-style
pass fusion), so a bracketing search costs about half the passes of the
one-trial-per-pass loop while examining the identical candidate sequence.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.analysis import sanitizers
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.data.prefetch import TransferStats, run_prefetched
from photon_ml_tpu.data.staging import COMPRESSION_MODES, plan_compression
from photon_ml_tpu.data.streaming import StreamingGlmData
from jax import shard_map
from photon_ml_tpu.optim.lbfgs import (
    LBFGSConfig,
    SolveResult,
    _two_loop,
    update_history,
)
from photon_ml_tpu.optim.linesearch import LineSearchConfig
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.optim.owlqn import OWLQNConfig, _pseudo_gradient

Array = jax.Array

#: candidate steps per batched weak-Wolfe pass: the current trial plus its
#: two possible bisection successors (see ``_host_wolfe``).
_WOLFE_TRIAL_BATCH = 3
#: candidate steps per batched OWL-QN Armijo pass (the geometric
#: backtracking ladder is fully deterministic, so any prefix batches).
_OWLQN_TRIAL_BATCH = 4


# ---------------------------------------------------------------------------
# Importance-aware HBM working set: hot chunks skip pack + transfer
# ---------------------------------------------------------------------------


class HotChunkCache:
    """Byte-budgeted resident working set of streamed chunk items.

    The DuHL idea (arXiv:1708.05357, PAPERS.md) applied to the chunk
    stream: keep the most-influential chunks RESIDENT in HBM and stream
    only the cold tail.  Importance is re-derived every accumulation
    pass, for free, from the per-chunk deltas of the value accumulator
    the streamed carry already computes — no extra device work.  A hot
    hit returns the (wire) device buffers directly, skipping pack,
    ``device_put`` and the transfer wait entirely; the SAME compiled
    per-chunk program serves hot and cold items, so results stay
    bitwise identical to the uncached path (accumulation order remains
    strictly chunk-sequential — the consumer interleaves hot hits into
    their global positions).

    Admission is one pass deferred by construction: pass N's scores
    pick the wanted set (:meth:`replan`), pass N+1 admits those items'
    device buffers as they stream by, pass N+2 onward hits.  Ties in
    the importance score break by item index, so admission is
    deterministic under equal scores (pinned by tests).

    The lock guards pure bookkeeping only (dict/set/counter updates);
    evicted device references are collected under the lock but DROPPED
    outside it, so buffer deallocation never runs in a critical section
    (the lock-blocking-call rule in analysis/ checks this discipline).
    Entries are never donated to XLA — chunk arguments are not in any
    program's ``donate_argnums`` — so a resident buffer stays valid
    across passes.

    With ``n_devices > 1`` the cached buffers are mesh-sharded, so a
    resident item pins only ``ceil(nbytes / n_devices)`` bytes on EACH
    device; ``budget_bytes`` then bounds the PER-DEVICE resident bytes
    (the quantity that actually competes with program HBM), not the
    logical total.  Admission/replan arithmetic uses that per-device
    cost throughout — the same budget number means the same per-device
    pressure whether the stream is sharded or not.
    """

    def __init__(self, budget_bytes: int, n_devices: int = 1):
        self.budget_bytes = int(budget_bytes)
        self.n_devices = max(1, int(n_devices))
        self._lock = sanitizers.tracked(
            threading.Lock(), "streaming.hot_cache"
        )
        self._entries: dict = {}  # item index -> (device bufs, nbytes)
        self._want: set = set()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.admissions = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def get(self, i: int):
        """Resident device buffers for item ``i``, or None (counted)."""
        with self._lock:
            e = self._entries.get(i)
            if e is None:
                self.misses += 1
                return None
            self.hits += 1
            return e[0]

    def maybe_admit(self, i: int, dev, nbytes: int) -> bool:
        """Admit item ``i``'s just-transferred device buffers iff the
        last replan wants it and it fits the remaining budget."""
        cost = -(-int(nbytes) // self.n_devices)  # per-device ceil
        with self._lock:
            if i in self._entries or i not in self._want:
                return False
            if self._bytes + cost > self.budget_bytes:
                return False
            self._entries[i] = (dev, cost)
            self._bytes += cost
            self.admissions += 1
            return True

    def replan(self, scores: dict, item_nbytes: Callable[[int], int]):
        """Recompute the wanted set from this pass's importance scores
        and evict residents that fell out of it.

        Greedy by descending score (ties broken by ascending item index
        — deterministic), packing until the byte budget is exhausted.
        On an injected eviction fault the cache is CLEARED before the
        fault propagates: a half-applied plan may never survive into
        the next pass (which then simply streams everything — results
        are unaffected either way, only transfer counts).
        """
        try:
            chaos_mod.maybe_fail("streaming.cache_evict")
        except BaseException:
            self.clear()
            raise
        dropped = []
        with self._lock:
            want: set = set()
            budget = self.budget_bytes
            for i in sorted(scores, key=lambda j: (-scores[j], j)):
                nb = -(-int(item_nbytes(i)) // self.n_devices)
                if nb <= budget:
                    want.add(i)
                    budget -= nb
            self._want = want
            for i in [j for j in self._entries if j not in want]:
                dev, nb = self._entries.pop(i)
                self._bytes -= nb
                self.evictions += 1
                dropped.append(dev)
        del dropped  # device refs released outside the lock

    def clear(self) -> None:
        with self._lock:
            dropped = list(self._entries.values())
            self._entries.clear()
            self._want = set()
            self._bytes = 0
        del dropped


# ---------------------------------------------------------------------------
# Streamed objective: value+grad as one pass over host chunks
# ---------------------------------------------------------------------------


class StreamingObjective:
    """A GlmObjective evaluated by streaming host chunks through the device.

    ``accumulate``: "f32" adds chunk contributions directly; "kahan"
    carries a compensation term per accumulator (value and gradient), so
    the cross-chunk summation error stays O(ε) instead of O(n_chunks·ε) —
    the scale-robust option for very long streams (the reference
    accumulates in f64 via Breeze; TPUs have no fast f64, compensation is
    the idiomatic equivalent).

    With ``mesh`` (and chunks built with ``n_shards == mesh size``) each
    chunk is placed sharded over the mesh's first axis and the per-chunk
    reduction runs under ``shard_map`` with one fused psum — streamed data
    parallelism.

    Transfers ride the coalesced ingest pipeline: each chunk moves as a
    few large dtype-segregated staging buffers (data/staging.py) whose
    compiled unpack is traced into the per-chunk program, and two
    background threads (pack + transfer, data/prefetch.py) keep
    ``prefetch_depth`` chunks in flight while the consumer syncs on a
    bounded window of carries — pack, transfer and compute overlap, and
    results stay bit-identical to ``prefetch_depth=1`` because the f32
    accumulation order is per-chunk-sequential either way.  HBM holds at
    most ``2·prefetch_depth`` chunks (``prefetch_depth`` transferred-not-
    consumed + a ``prefetch_depth``-deep window of dispatched-not-synced
    programs), times ``chunk_fuse`` when fusing.

    ``chunk_fuse > 1`` stacks that many chunks per transfer and folds
    them on device with ``lax.scan`` (one dispatch per group, same
    accumulation order) — for stores whose chunks are small enough that
    per-dispatch overhead dominates.  Single-device only (no mesh), and
    requires the staged (coalesced-buffer) representation.

    ``transfer_stats`` accumulates per-chunk h2d timing, achieved GB/s,
    per-stage wall attribution (pack/dispatch/h2d/consume) and
    queue-stall counters across passes — reset it around a measurement
    window.

    ``compress`` (off|lossless|fp16|int8) turns on the compressed chunk
    wire formats (data/staging.py): chunks cross the link as encoded
    wire buffers 2–4× smaller and are decoded ON DEVICE by the dequant
    step traced into each per-chunk program.  "lossless" keeps every
    streamed result BITWISE identical to the raw path; fp16/int8
    additionally quantize float feature values (bounded error, pinned
    by tests).  Requires the staged representation and a single-host
    run (per-process compression plans would compile divergent SPMD
    executables on a pod).  ``transfer_stats.bytes`` stays WIRE bytes;
    ``logical_bytes`` carries the decoded total.

    ``hot_budget_bytes`` > 0 enables the importance-aware HBM working
    set (:class:`HotChunkCache`): up to that many bytes of (wire)
    chunk buffers stay RESIDENT across passes, re-chosen each
    accumulation pass from per-chunk gradient-contribution importance,
    and hot chunks skip pack + transfer entirely.  Single-device only.
    Results are bitwise identical to the uncached path — the cache
    only changes which chunks cross the link, never the accumulation
    order.  (``scores()`` always streams: its readback pipeline does
    not consult the cache.)
    """

    def __init__(
        self,
        task_or_objective,
        stream: StreamingGlmData,
        normalization=None,
        mesh=None,
        accumulate: str = "f32",
        prefetch_depth: int = 2,
        chunk_fuse: int = 1,
        compress: str = "off",
        hot_budget_bytes: int = 0,
    ):
        from photon_ml_tpu.ops import losses as losses_lib

        if isinstance(task_or_objective, GlmObjective):
            self.objective = task_or_objective
        else:
            self.objective = GlmObjective(
                losses_lib.get(task_or_objective), normalization
            )
        if accumulate not in ("f32", "kahan"):
            raise ValueError(f"accumulate must be f32|kahan, got {accumulate}")
        if prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {prefetch_depth}"
            )
        if chunk_fuse < 1:
            raise ValueError(f"chunk_fuse must be >= 1, got {chunk_fuse}")
        if chunk_fuse > 1 and mesh is not None:
            raise ValueError(
                "chunk_fuse > 1 is single-device only: the scan-fused "
                "program is not composed with the shard_map reduction — "
                "pass chunk_fuse=1 with a mesh"
            )
        if compress not in COMPRESSION_MODES:
            raise ValueError(
                f"compress must be one of {COMPRESSION_MODES}, got "
                f"{compress!r}"
            )
        if hot_budget_bytes < 0:
            raise ValueError(
                f"hot_budget_bytes must be >= 0, got {hot_budget_bytes}"
            )
        if hot_budget_bytes and mesh is not None and jax.process_count() > 1:
            raise ValueError(
                "the hot working-set cache is single-host only: on a "
                "pod each process would pin a divergent resident set "
                "and the SPMD dispatch order would skew across hosts — "
                "pass hot_budget_bytes=0 in multi-host mode"
            )
        self.stream = stream
        self.mesh = mesh
        self.accumulate = accumulate
        self.prefetch_depth = int(prefetch_depth)
        self.chunk_fuse = int(chunk_fuse)
        self.transfer_stats = TransferStats()
        # Coalesce to staging buffers (no-op when the builder already
        # did); falls back to per-leaf pytree transfers only for
        # hand-built disk-backed stores, which cannot pack in RAM.
        stream.ensure_staged()
        self._staging = stream.staging
        if self.chunk_fuse > 1 and stream.staged is None:
            raise ValueError(
                "chunk_fuse > 1 needs the staged (coalesced-buffer) "
                "representation — this store could not be staged "
                "(hand-built disk-backed per-leaf store?)"
            )
        # Fused transfer groups: consecutive chunk ranges of chunk_fuse
        # (the last one ragged).  With chunk_fuse == 1 the pipeline runs
        # per chunk and this grouping is the identity.
        n_ch = stream.n_chunks
        fuse = min(self.chunk_fuse, max(n_ch, 1))
        self._groups = [
            range(lo, min(lo + fuse, n_ch)) for lo in range(0, n_ch, fuse)
        ]
        self._sharding = None
        # Multi-host (pod) mode: every process holds a chunk store over
        # ITS host-local rows only (n_shards = local device count) and
        # feeds just its own shards of each globally-sharded chunk — the
        # streamed analogue of multihost.assemble_global, so no host ever
        # materializes a global chunk.  Row order across hosts differs
        # from the single-host layout, which is immaterial: every
        # streamed reduction is a permutation-invariant sum over rows.
        self._multihost = jax.process_count() > 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            expect = (
                jax.local_device_count() if self._multihost
                else mesh.devices.size
            )
            if stream.n_shards != expect:
                raise ValueError(
                    f"stream has n_shards={stream.n_shards}; this "
                    f"{'process' if self._multihost else 'mesh'} needs "
                    f"{expect}"
                )
            if stream.n_shards == 1 and not self._multihost:
                # Single-shard chunks carry NO shard axis (data/streaming
                # builds the stacked layout only for n_shards > 1).  The
                # mesh path's x[0] unstack would then strip a DATA axis
                # and silently compute the objective over wrong slices —
                # no error, wrong numbers (verified).  Refuse loudly.
                raise ValueError(
                    "single-shard chunks carry no shard axis; the mesh "
                    "path would silently compute over wrong data — pass "
                    "mesh=None for single-device streams"
                )
            if stream.n_shards == 1 and self._multihost:
                raise ValueError(
                    "multi-host streams need n_shards == "
                    "jax.local_device_count() > 1 per process; a "
                    "1-local-device pod member is unsupported"
                )
            if self._multihost:
                self._align_multihost_chunks()
            self._axis = mesh.axis_names[0]
            self._sharding = NamedSharding(mesh, P(self._axis))
        elif stream.n_shards != 1:
            raise ValueError("sharded chunks need a mesh")

        # Compressed chunk formats: plan one codec over the whole store
        # (AFTER any multihost equalization so padding chunks are
        # scanned too), encode every chunk's wire buffers eagerly (host
        # RAM cost ≈ staged bytes / ratio — the raw staged store stays
        # the source of truth for host-side views), and route the
        # per-chunk unpack through the codec's on-device decode.
        self.compress = compress
        self._codec = None
        self._wire = None
        if compress != "off":
            if stream.staged is None:
                raise ValueError(
                    "compress != 'off' needs the staged (coalesced-"
                    "buffer) representation — this store could not be "
                    "staged (hand-built disk-backed per-leaf store?)"
                )
            if self._multihost:
                raise ValueError(
                    "compress != 'off' is single-host only: each "
                    "process would plan its own encodings from its own "
                    "rows and compile divergent SPMD executables — "
                    "pass compress='off' on a pod"
                )
            self._codec = plan_compression(
                self._staging, stream.staged, compress
            )
            self._wire = [
                self._codec.encode(bufs) for bufs in stream.staged
            ]
        # Importance-aware HBM working set (see class docstring for the
        # admit-next-pass lifecycle).  Under a mesh the cached buffers
        # are the sharded wire trees, so the budget counts per-device
        # bytes — n_devices divides each entry's cost.
        self.hot_budget_bytes = int(hot_budget_bytes)
        if hot_budget_bytes and stream.staged is None:
            raise ValueError(
                "hot_budget_bytes > 0 needs the staged representation "
                "(byte-budgeted admission requires the fixed per-chunk "
                "staged size)"
            )
        self._hot_cache = (
            HotChunkCache(
                hot_budget_bytes,
                n_devices=(1 if mesh is None else int(mesh.devices.size)),
            )
            if hot_budget_bytes
            else None
        )

        obj = self.objective
        staging = self._staging
        codec = self._codec

        def unpack(chunk_in):
            # The compiled on-device unpack (slice + reshape) restoring
            # the GlmData view from the coalesced staging buffers —
            # traced INTO each per-chunk program, so coalescing costs no
            # extra dispatch.  Identity for unstaged (fallback) streams.
            # Under shard_map the buffers arrive as per-device blocks;
            # unpack_device reads the local leading dim off the trace.
            # With a codec the arriving buffers are the COMPRESSED wire
            # buffers and this is the in-program dequant step (slice +
            # cast + cumsum/shift), same relative-slicing contract.
            if codec is not None:
                return codec.unpack_device(chunk_in)
            if staging is None:
                return chunk_in
            return staging.unpack_device(chunk_in)

        def chunk_vg(w, off, chunk):
            # ``off``: extra per-row margin offsets (coordinate descent —
            # the other coordinates' scores); a traced scalar 0 when
            # absent, so the plain-GLM trace carries no extra transfer.
            # Under a mesh, a non-scalar ``off`` arrives SHARDED like the
            # chunk (leading shard axis) — the streamed-GAME × DP
            # composition.
            chunk = unpack(chunk)
            if mesh is not None:
                local = jax.tree.map(lambda x: x[0], chunk)
                off_local = off if off.ndim == 0 else off[0]
                local = dataclasses.replace(
                    local, offsets=local.offsets + off_local
                )
                v, g = obj.raw_value_and_grad(w, local)
                return lax.psum(v, self._axis), lax.psum(g, self._axis)
            chunk = dataclasses.replace(chunk, offsets=chunk.offsets + off)
            return obj.raw_value_and_grad(w, chunk)

        def chunk_hvp(w, v, off, chunk):
            # Recomputes the d2 weights inside the chunk program (one extra
            # margins matvec) — the streamed analogue of the reference's
            # HessianVectorAggregator, which recomputes per-row d2 on every
            # treeAggregate round (SURVEY.md §3.1).  The resident TRON's
            # per-iterate d2 cache (optim/tron.py) is an HBM-resident
            # luxury the chunk store deliberately forgoes: caching would
            # mean either holding n_rows of d2 weights in HBM (not
            # out-of-core) or round-tripping them host↔device per CG step.
            chunk = unpack(chunk)
            if mesh is not None:
                local = jax.tree.map(lambda x: x[0], chunk)
                off_local = off if off.ndim == 0 else off[0]
                local = dataclasses.replace(
                    local, offsets=local.offsets + off_local
                )
                return lax.psum(obj.raw_hvp(w, v, local), self._axis)
            chunk = dataclasses.replace(chunk, offsets=chunk.offsets + off)
            return obj.raw_hvp(w, v, chunk)

        def chunk_diag(w, off, chunk):
            chunk = unpack(chunk)
            if mesh is not None:
                local = jax.tree.map(lambda x: x[0], chunk)
                off_local = off if off.ndim == 0 else off[0]
                local = dataclasses.replace(
                    local, offsets=local.offsets + off_local
                )
                d2w = obj.d2_weights(w, local)
                return lax.psum(
                    local.features.sq_rmatvec(d2w), self._axis
                )
            chunk = dataclasses.replace(chunk, offsets=chunk.offsets + off)
            d2w = obj.d2_weights(w, chunk)
            return chunk.features.sq_rmatvec(d2w)

        def score_step(w, chunk):
            chunk = unpack(chunk)
            if mesh is not None:
                local = jax.tree.map(lambda x: x[0], chunk)
                return obj.margins(w, local)
            return obj.margins(w, chunk)

        def acc_update(carry, v, g):
            # The f32/kahan accumulator fold of ONE candidate: the batched
            # step calls it once per row of its ((K,)/(K,d)) carry.
            if accumulate == "f32":
                vacc, gacc = carry
                return (vacc + v, gacc + g)
            vacc, vc, gacc, gc = carry
            yv = v - vc
            tv = vacc + yv
            vc = (tv - vacc) - yv
            yg = g - gc
            tg = gacc + yg
            gc = (tg - gacc) - yg
            return (tv, vc, tg, gc)

        def hvp_update(carry, h):
            if accumulate == "f32":
                return (carry[0] + h,)
            hacc, hc = carry
            yh = h - hc
            th = hacc + yh
            return (th, (th - hacc) - yh)

        # Flattened step functions: ``step(*carry, *args, off, chunk) ->
        # carry tuple``.  The carry is flattened into SEPARATE positional
        # args so donation can target just the gradient accumulators
        # (donate_argnums is per-argument) while the value scalar stays
        # un-donated — it is the windowed-sync handle _stream_accumulate
        # blocks on (a donated buffer cannot be synced: it is deleted the
        # moment the next step consumes it).
        self._n_carry = {
            "acc": 2 if accumulate == "f32" else 4,
            "hvp": 1 if accumulate == "f32" else 2,
            "diag": 1,
        }
        self._n_args = {"acc": 1, "hvp": 2, "diag": 1}
        # Gradient/HVP accumulators update IN PLACE via buffer donation.
        # The value scalar (leaf 0 of "acc") is deliberately NOT donated:
        # it is the sync handle.  "hvp"/"diag" carries are their own sync
        # handles, so they are not donated either.
        self._donate = {
            "acc": (1,) if accumulate == "f32" else (2, 3),
            "hvp": (),
            "diag": (),
        }

        def make_step(kind: str, batch: int | None):
            nc = self._n_carry[kind]

            def step(*fl):
                carry = fl[:nc]
                off, chunk = fl[-2], fl[-1]
                if kind == "acc":
                    w = fl[nc]
                    if batch is None:
                        return acc_update(
                            carry, *chunk_vg(w, off, chunk)
                        )
                    # UNROLLED over the K candidates, not vmapped, and
                    # each candidate folded into ITS OWN accumulator row
                    # before the rows are stacked: every candidate then
                    # runs the exact graph of the single-w program, fold
                    # included, so a batched trial matches a sequential
                    # trial bitwise.  (vmap would re-block the matvecs by
                    # batch shape; stacking first and folding the (K, d)
                    # block once lets the compiler sum the chunk's
                    # gradient straight into the single-w accumulator
                    # but not into the stacked one — another order.)
                    outs = [
                        acc_update(
                            tuple(c[i] for c in carry),
                            *chunk_vg(w[i], off, chunk),
                        )
                        for i in range(batch)
                    ]
                    return tuple(jnp.stack(c) for c in zip(*outs))
                if kind == "hvp":
                    w, vec = fl[nc], fl[nc + 1]
                    return hvp_update(carry, chunk_hvp(w, vec, off, chunk))
                diag = carry[0]
                w = fl[nc]
                return (diag + chunk_diag(w, off, chunk),)

            return step

        def fuse_step(step, kind: str, n_fused: int):
            nc = self._n_carry[kind]
            na = self._n_args[kind]

            def fused(*fl):
                carry = tuple(fl[:nc])
                rest = fl[nc:nc + na]
                off, chunk = fl[-2], fl[-1]

                def body(c, xs):
                    o, b = xs
                    return tuple(step(*c, *rest, o, b)), None

                out, _ = lax.scan(body, carry, (off, chunk), length=n_fused)
                return out

            return fused

        self._make_step = make_step
        self._fuse_step = fuse_step
        self._score_step = score_step
        self._progs: dict = {}

        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            self._chunk_spec = P(self._axis)
            self._score = jax.jit(shard_map(
                score_step, mesh=mesh,
                in_specs=(P(), self._chunk_spec), out_specs=self._chunk_spec,
                check_vma=False,
            ))
        else:
            self._score = jax.jit(score_step)
        self._finish = jax.jit(
            lambda v, g, w, l2: (
                v + 0.5 * l2 * jnp.dot(w, w), g + l2 * w
            )
        )
        self._finish_batch = jax.jit(
            lambda v, g, w, l2: (
                v + 0.5 * l2 * jnp.einsum("kd,kd->k", w, w), g + l2 * w
            )
        )
        self._hvp_finish = jax.jit(lambda h, v, l2: h + l2 * v)

    @property
    def n_features(self) -> int:
        return self.stream.n_features

    def _program(self, kind: str, n_fused: int = 1, batch: int | None = None,
                 row_off: bool = False) -> Callable:
        """The compiled per-item program for pass ``kind`` — built lazily
        and cached per (fused length, trial-batch width, offset kind).
        One compile per solve in the common case; a ragged fused tail
        adds one more."""
        key = (kind, n_fused, batch, row_off)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        step = self._make_step(kind, batch)
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            nc = self._n_carry[kind]
            na = self._n_args[kind]
            carry_specs = (P(),) * nc
            off_spec = self._chunk_spec if row_off else P()
            step = shard_map(
                step, mesh=self.mesh,
                in_specs=carry_specs + (P(),) * na
                + (off_spec, self._chunk_spec),
                out_specs=carry_specs, check_vma=False,
            )
        elif n_fused > 1:
            step = self._fuse_step(step, kind, n_fused)
        prog = jax.jit(step, donate_argnums=self._donate[kind])
        self._progs[key] = prog
        return prog

    def _align_multihost_chunks(self) -> None:
        """Pod-wide agreement checks the streamed loop's collectives need.

        Every process runs one psum per chunk, so (a) chunk COUNTS must
        match — an uneven ``host_local_rows`` split is equalized by
        appending all-padding (zero-weight) chunks locally, which add
        exactly zero to every reduction; (b) chunk leaf SHAPES must match
        — each process's store pads to its OWN nnz budget / layout, and a
        mismatch would compile different SPMD executables per process
        (hang or crash deep in XLA), so it is refused loudly here with
        the fix spelled out."""
        import zlib

        from jax.experimental import multihost_utils

        chunks = self.stream.chunks
        leaves = jax.tree.leaves(chunks[0])
        # The structure signature is hashed to a SCALAR before the
        # allgather: a raw per-leaf shape vector would have a
        # process-dependent LENGTH exactly when structures mismatch, and
        # process_allgather on ragged inputs dies (or hangs) deep in the
        # collective instead of reaching the explanatory error below.
        shape_sig = ",".join(
            f"{len(leaf.shape)}:{leaf.shape}" for leaf in leaves
        )
        crc = zlib.crc32(f"{len(leaves)}|{shape_sig}".encode())
        sig = np.asarray([len(chunks), crc], np.int64)
        all_sigs = np.asarray(multihost_utils.process_allgather(sig))
        if not (all_sigs[1:, 1] == all_sigs[0, 1]).all():
            raise ValueError(
                "multi-host chunk stores have mismatched leaf shapes "
                "across processes (per-process nnz budgets / layouts "
                "differ) — build every process's store with the same "
                "chunk_rows and a COMMON coo_budget "
                "(make_streaming_glm_data(..., coo_budget=N)), and "
                "use_pallas=False"
            )
        max_chunks = int(all_sigs[:, 0].max())
        if len(chunks) < max_chunks:
            pad = max_chunks - len(chunks)
            if self.stream.staged is not None:
                # Equalization chunks ride the staged representation
                # too: one shared all-zero buffer set (read-only) and a
                # view over it, so every transfer path stays coalesced.
                blank_bufs = tuple(
                    np.zeros_like(np.asarray(b))
                    for b in self.stream.staged[0]
                )
                blank = self.stream.staging.view(blank_bufs)
                self.stream.staged = (
                    list(self.stream.staged) + [blank_bufs] * pad
                )
            else:
                blank = jax.tree.map(np.zeros_like, chunks[0])
            self.stream.chunks = chunks + [blank] * pad
        # The fused grouping is sized off n_chunks; re-derive after any
        # equalization padding (fusion is single-device-only today, but
        # keep the invariant locally true).
        n_ch = self.stream.n_chunks
        fuse = min(self.chunk_fuse, max(n_ch, 1))
        self._groups = [
            range(lo, min(lo + fuse, n_ch)) for lo in range(0, n_ch, fuse)
        ]

    def _put_local_block(self, x) -> Array:
        """Assemble one globally-sharded array from THIS process's local
        shard block (multihost.assemble_global's contract): global shard
        axis = processes x local shards, this process's block slotting in
        at its process index."""
        total = self.mesh.devices.size
        gshape = (total,) + tuple(x.shape[1:])
        return jax.make_array_from_process_local_data(
            self._sharding, np.asarray(x), gshape
        )

    def _put(self, chunk):
        chaos_mod.maybe_fail("staging.put")
        if self._sharding is not None:
            if self._multihost:
                # Each process contributes ONLY its local shard block of
                # the global chunk, per leaf.
                return jax.tree.map(self._put_local_block, chunk)
            return jax.device_put(chunk, self._sharding)
        return jax.device_put(chunk)

    def offset_slices(self, offsets) -> list:
        """Per-chunk slices of coordinate-descent offsets (the other
        coordinates' scores), zero-padded to the chunk grid; a traced
        scalar 0 per chunk when absent (no extra transfer, own trace).
        Callers evaluating many passes against FIXED offsets (a whole
        L-BFGS solve) should call this once and pass the list to
        ``value_and_grad`` — it is accepted in place of the raw array."""
        if isinstance(offsets, list):  # already sliced
            return offsets
        cr = self.stream.chunk_rows
        n_chunks = self.stream.n_chunks
        if offsets is None:
            zero = jnp.zeros((), jnp.float32)
            return [zero] * n_chunks
        if offsets.shape[0] != self.stream.n_rows:
            # A silently zero-padded short array would train the tail rows
            # against offset 0 and converge to a wrong model.
            raise ValueError(
                f"offsets has {offsets.shape[0]} rows; the stream has "
                f"{self.stream.n_rows}"
            )
        if self.mesh is not None:
            # Streamed GAME × DP: each chunk's offset slice is reshaped to
            # the chunk's (shard, row) grid and placed SHARDED over the
            # mesh, so the per-chunk program adds it to the local rows with
            # no gather (row k of shard s is chunk row s·per_shard + k,
            # matching data/streaming's reshape layout).
            #
            # On a POD, per-row CD state is PROCESS-LOCAL (the reference's
            # layout: score RDDs live partitioned next to the data): the
            # offsets are THIS PROCESS's rows — exactly the rows its chunk
            # store holds — and each reshaped slice feeds only the local
            # shard block of the global chunk, the same assemble_global
            # contract the data chunks use.  Blank equalization chunks
            # (appended past the local rows) get zero offsets from the
            # padding below, matching their zero weights.
            n_sh = self.stream.n_shards
            off = np.asarray(offsets, np.float32)
            pad = n_chunks * cr - off.shape[0]
            if pad:
                off = np.pad(off, (0, pad))
            blocks = [
                off[k * cr:(k + 1) * cr].reshape(n_sh, cr // n_sh)
                for k in range(n_chunks)
            ]
            if self._multihost:
                return [self._put_local_block(b) for b in blocks]
            return [
                jax.device_put(b, self._sharding) for b in blocks
            ]
        off = jnp.asarray(offsets, jnp.float32)
        pad = n_chunks * cr - off.shape[0]
        if pad:
            off = jnp.pad(off, (0, pad))
        return [off[k * cr:(k + 1) * cr] for k in range(n_chunks)]

    def _host_item(self, k: int):
        """What crosses the wire for chunk ``k``: the encoded wire
        buffers when compressing, else the coalesced staging buffers
        when the store is staged, else the leaf pytree."""
        if self._wire is not None:
            return self._wire[k]
        if self.stream.staged is not None:
            return self.stream.staged[k]
        return self.stream.chunks[k]

    def _fused_host_item(self, g: int):
        """Fused group ``g``'s transfer item: the group's staging buffers
        stacked on a new leading chunk axis (the scan axis of the fused
        program).  The stack is a transient host copy that runs on the
        PACK thread, where it overlaps both the link and device compute;
        memmapped (disk-backed) buffers page in here too.  A singleton
        group (the ragged tail) stays a plain un-stacked chunk item and
        runs the ordinary per-chunk program."""
        ks = self._groups[g]
        staged = (
            self._wire if self._wire is not None else self.stream.staged
        )
        if len(ks) == 1:
            return staged[ks[0]]
        n_buf = len(staged[ks[0]])
        return tuple(
            np.stack([np.asarray(staged[k][b]) for k in ks])
            for b in range(n_buf)
        )

    def _group_offsets(self, slices: list) -> list:
        """Per-ITEM offsets under fusion: each group's per-chunk slices
        stacked on the scan axis (identity when chunk_fuse == 1;
        singleton groups keep their plain per-chunk slice)."""
        if self.chunk_fuse == 1:
            return slices
        return [
            slices[grp[0]] if len(grp) == 1
            else jnp.stack([slices[k] for k in grp])
            for grp in self._groups
        ]

    def _stream_accumulate(self, kind: str, init: tuple, args=(),
                           per_chunk=None, batch: int | None = None):
        """Run ``carry = prog(*carry, *args, off_i, item_i)`` over all
        chunks (or fused chunk groups) through the prefetch pipeline,
        syncing on a bounded WINDOW of carries.

        The pack and transfer threads keep ``prefetch_depth`` items in
        flight (data/prefetch.py); the consumer dispatches item k's
        program and then blocks only on item ``k - prefetch_depth``'s
        sync handle, so the device always has up to ``prefetch_depth``
        programs queued behind the executing one and never idles during
        a chunk's Python dispatch.  The window is the backpressure that
        bounds HBM residency: a dispatched-but-unexecuted program pins
        its chunk's buffers, so ≤ ``2·prefetch_depth`` chunk groups are
        ever live (``prefetch_depth`` un-consumed transfers + the
        window).  ``prefetch_depth=1`` degrades to the fully-serial
        sync-every-chunk baseline.  The sync handle is carry leaf 0,
        which is never donated (see ``__init__``); gradient accumulators
        ARE donated, updating in place.  Accumulation order is strictly
        chunk-sequential regardless of depth/window/fusion — results are
        bit-identical across all of them on f32.

        With the hot working-set cache enabled, resident items bypass
        the pipeline entirely: only the cold tail rides
        ``run_prefetched``, and the consumer interleaves each hot
        item's dispatch at its exact global position before the next
        cold item — the accumulation order (and therefore every f32
        bit) is unchanged.  On "acc" passes the synced carry handles
        double as the importance source: |Δvalue| per item scores the
        pass for free, and the cache replans (admit set + evictions)
        ONCE at pass end.
        """
        if self.chunk_fuse == 1:
            n_items = self.stream.n_chunks
            get_host = self._host_item
            items_off = per_chunk
            lens = None  # all programs identical
        else:
            n_items = len(self._groups)
            get_host = self._fused_host_item
            items_off = self._group_offsets(per_chunk)
            lens = [len(g) for g in self._groups]
        row_off = (
            self.mesh is not None
            and getattr(per_chunk[0], "ndim", 0) != 0
        )
        if lens is None:
            prog = self._program(kind, 1, batch, row_off)
            progs = [prog] * n_items
        else:
            progs = [
                self._program(kind, L, batch, row_off) for L in lens
            ]
        window = 0 if self.prefetch_depth == 1 else self.prefetch_depth
        carry_box = [tuple(init)]
        ring: collections.deque = collections.deque()
        ring_peak = 0
        stats = self.transfer_stats
        bytes0, chunks0 = stats.bytes, stats.chunks
        codec = self._codec
        cache = self._hot_cache
        hot0 = (
            (cache.hits, cache.misses, cache.admissions, cache.evictions)
            if cache is not None else None
        )
        st_nbytes = self._staging.nbytes if self._staging else 0

        def item_logical(i: int) -> int:
            # Decoded (staged) bytes item i stands for; × group length
            # under fusion.
            return st_nbytes * (lens[i] if lens else 1)

        def item_wire(i: int) -> int:
            wb = codec.wire_nbytes if codec is not None else st_nbytes
            return wb * (lens[i] if lens else 1)

        t_pass0 = time.perf_counter()
        # Importance scoring: only accumulation passes carry a scalar
        # value whose per-item delta is the chunk's contribution (hvp/
        # diag carries are vectors) — other kinds still SERVE hits, they
        # just don't replan.
        scoring = {} if (cache is not None and kind == "acc") else None
        vprev = [0.0]

        def sync_handle(entry):
            i, h = entry
            jax.block_until_ready(h)
            if scoring is not None:
                # |Δvalue| this item added to the running accumulator —
                # free importance (the handle is already synced; the
                # readback is one scalar, K for batched trials where
                # candidate 0 — the current iterate — scores).
                v = float(np.asarray(h).reshape(-1)[0])
                scoring[i] = abs(v - vprev[0])
                vprev[0] = v

        def dispatch(i, dev):
            # One item's program dispatch + windowed sync, identical
            # for hot (cache-resident) and cold (just-transferred)
            # items — the shared path is what keeps hot/cold bitwise
            # interchangeable.
            nonlocal ring_peak
            if codec is not None:
                chaos_mod.maybe_fail("staging.decode", item=i)
            chaos_mod.maybe_fail("streaming.carry_sync", item=i)
            carry_box[0] = progs[i](
                *carry_box[0], *args, items_off[i], dev
            )
            ring.append((i, carry_box[0][0]))
            if len(ring) > window:
                sync_handle(ring.popleft())
            # Post-sync occupancy: dispatched-but-unexecuted programs
            # still pinning their chunk buffers (the popped handle just
            # proved its chunk executed).
            ring_peak = max(ring_peak, len(ring))

        # Hot/cold split for this pass: resident items skip pack +
        # transfer; the cold tail streams.  The gather is one locked
        # dict probe per item, before any thread starts.
        hot: dict = {}
        if cache is not None:
            for i in range(n_items):
                d = cache.get(i)
                if d is not None:
                    hot[i] = d
        cold = [i for i in range(n_items) if i not in hot]
        next_i = [0]  # next global item index still to dispatch

        def advance_hot(upto: int) -> None:
            # Dispatch every not-yet-dispatched HOT item below ``upto``
            # — called before each cold item (and once at the end) so
            # the global dispatch order is exactly 0..n_items-1.
            while next_i[0] < upto:
                j = next_i[0]
                if j in hot:
                    dispatch(j, hot[j])
                next_i[0] = j + 1

        def consume(ci, dev):
            i = cold[ci]
            advance_hot(i)
            dispatch(i, dev)
            next_i[0] = i + 1
            if cache is not None:
                cache.maybe_admit(i, dev, item_wire(i))

        run_max = run_prefetched(
            len(cold), lambda ci: get_host(cold[ci]), self._put, consume,
            depth=self.prefetch_depth, stats=stats,
            logical_nbytes=(
                (lambda ci: item_logical(cold[ci]))
                if codec is not None else None
            ),
        )
        advance_hot(n_items)  # trailing hot items past the last cold one
        while ring:
            # Drain: the carry chain is sequential, so the LAST handle's
            # readiness implies every chunk executed (and every chunk
            # buffer is collectable) before the pass returns.  When
            # scoring, each handle is read back in order instead.
            entry = ring.popleft()
            if scoring is not None or not ring:
                sync_handle(entry)
        if scoring:
            # Admission is one pass deferred: this replan's wanted set
            # admits during the NEXT pass's stream.  A chaos eviction
            # fault propagates from here (cache already cleared).
            cache.replan(scoring, item_wire)
        # HBM accounting for the carry window (docs/telemetry.md "HBM
        # accounting"): a dispatched-but-unexecuted program pins its
        # chunk's buffers beyond the prefetch permit, so the pass's true
        # staged-buffer residency peak is (live transfers + window
        # occupancy) x per-chunk staged bytes — the measured counterpart
        # of the documented <= 2·depth·chunk bound, and the number
        # ROADMAP item 1's working-set cache must beat.  One gauge write
        # per PASS, nothing per chunk.
        tel = telemetry_mod.current()
        if tel.enabled:
            # Every streamed pass is one logical all-reduce round: the
            # chunk-sequential accumulation folds a (batch × (d+1)) carry
            # exactly like a psum across shards.  Publishing it here puts
            # the on-device solvers on the same instrument the distributed
            # solvers (solvers/admm.py, solvers/block_cd.py) report on, so
            # reduces per solve compare across solver kinds.
            tel.counter("solver_allreduce_count").inc(1)
            tel.counter("solver_allreduce_bytes_total").inc(
                (batch or 1) * (self.stream.n_features + 1) * 4
            )
            d_chunks = stats.chunks - chunks0
            if d_chunks > 0:
                chunk_bytes = (stats.bytes - bytes0) / d_chunks
                tel.gauge("hbm_stream_chunk_bytes").set(int(chunk_bytes))
                tel.gauge("hbm_stream_window_peak_bytes").set(
                    int((run_max + ring_peak) * chunk_bytes)
                )
            if codec is not None or cache is not None:
                # Effective ingest rate: LOGICAL bytes of every item the
                # pass processed (hot hits move zero wire bytes but
                # stand for their full decoded size) over the pass wall
                # — the number compression + caching actually move,
                # where h2d_gbps honestly reports only the link.
                wall = time.perf_counter() - t_pass0
                if wall > 0.0:
                    tel.gauge("stream_effective_gbps").set(
                        sum(item_logical(i) for i in range(n_items))
                        / wall / 1e9
                    )
            if cache is not None:
                d_hit = cache.hits - hot0[0]
                d_miss = cache.misses - hot0[1]
                tel.counter("stream_hot_hits_total").inc(d_hit)
                tel.counter("stream_hot_misses_total").inc(d_miss)
                tel.counter("stream_hot_admissions_total").inc(
                    cache.admissions - hot0[2]
                )
                tel.counter("stream_hot_evictions_total").inc(
                    cache.evictions - hot0[3]
                )
                if d_hit + d_miss:
                    tel.gauge("stream_hot_hit_ratio").set(
                        d_hit / (d_hit + d_miss)
                    )
                tel.gauge("hbm_hot_bytes").set(cache.resident_bytes)
                tel.gauge("hbm_hot_budget_bytes").set(cache.budget_bytes)
                tel.gauge("hbm_hot_chunk_count").set(len(cache))
        return carry_box[0]

    def _acc_init(self, batch: int | None):
        d = self.stream.n_features
        shp_v = () if batch is None else (batch,)
        shp_g = (d,) if batch is None else (batch, d)
        if self.accumulate == "f32":
            return (jnp.zeros(shp_v, jnp.float32),
                    jnp.zeros(shp_g, jnp.float32))
        return (
            jnp.zeros(shp_v, jnp.float32), jnp.zeros(shp_v, jnp.float32),
            jnp.zeros(shp_g, jnp.float32), jnp.zeros(shp_g, jnp.float32),
        )

    def value_and_grad(
        self, w: Array, l2_weight=0.0, offsets=None
    ) -> tuple[Array, Array]:
        """One full streamed pass; returns device (value, grad) with the L2
        term applied.  ``offsets``: optional (n_rows,) extra margins added
        per row (coordinate descent)."""
        slices = self.offset_slices(offsets)
        out = self._stream_accumulate(
            "acc", self._acc_init(None), args=(w,), per_chunk=slices,
        )
        v, g = (out[0], out[1]) if self.accumulate == "f32" else (
            out[0], out[2]
        )
        return self._finish(v, g, w, jnp.asarray(l2_weight, jnp.float32))

    def value_and_grad_batch(
        self, ws: Array, l2_weight=0.0, offsets=None
    ) -> tuple[Array, Array]:
        """K objective evaluations in ONE streamed pass: ``ws`` is (K, d)
        candidate weight vectors (a line search's trial bracket), the
        per-chunk program evaluates all K against each chunk (unrolled,
        not vmapped — each candidate runs the exact single-w graph, so a
        batched trial is bitwise the sequential trial), and K (value,
        grad) accumulators ride one carry.  Returns ((K,), (K, d)) with
        the L2 term applied per candidate.  This is the vector-free
        L-BFGS pass-fusion trick: the line search streams the dataset
        once per BRACKET instead of once per trial."""
        ws = jnp.asarray(ws)
        if ws.ndim != 2:
            raise ValueError(
                f"value_and_grad_batch wants (K, n_features), got "
                f"{ws.shape}"
            )
        K = int(ws.shape[0])
        slices = self.offset_slices(offsets)
        out = self._stream_accumulate(
            "acc", self._acc_init(K), args=(ws,), per_chunk=slices,
            batch=K,
        )
        v, g = (out[0], out[1]) if self.accumulate == "f32" else (
            out[0], out[2]
        )
        return self._finish_batch(
            v, g, ws, jnp.asarray(l2_weight, jnp.float32)
        )

    def hessian_diagonal(self, w: Array, offsets=None) -> Array:
        """Σᵢ wᵢ·d2ᵢ·X²ᵢⱼ streamed over chunks (for coefficient variances)."""
        d = self.stream.n_features
        slices = self.offset_slices(offsets)
        return self._stream_accumulate(
            "diag", (jnp.zeros((d,), jnp.float32),),
            args=(w,), per_chunk=slices,
        )[0]

    def hvp(self, w: Array, v: Array, l2_weight=0.0, offsets=None) -> Array:
        """H(w)·v = Xᵀ(d2w ⊙ (Xv)) + λ·v as ONE streamed pass over the
        chunks — the ``HessianVectorAggregator`` ``treeAggregate`` round of
        the reference's distributed TRON (SURVEY.md §3.1), here a
        windowed-async chunk stream.  Callers issuing many HVPs against
        fixed offsets (a whole CG solve) should pre-slice via
        :meth:`offset_slices` and pass the list."""
        d = self.stream.n_features
        zero = jnp.zeros((d,), jnp.float32)
        init = (zero,) if self.accumulate == "f32" else (zero, zero)
        slices = self.offset_slices(offsets)
        h = self._stream_accumulate(
            "hvp", init, args=(w, v), per_chunk=slices,
        )[0]
        return self._hvp_finish(h, v, jnp.asarray(l2_weight, jnp.float32))

    def scores(self, w: Array) -> np.ndarray:
        """Margins for every row of THIS STORE, streamed, with the
        device→host readbacks pipelined: each chunk's margins start an
        ASYNC D2H copy at dispatch and materialize a window of
        ``prefetch_depth`` chunks behind, so readback latency overlaps
        the next chunks' transfer + compute instead of serializing the
        pass.

        On a pod the contract is PROCESS-LOCAL (the defined edge VERDICT
        r4 missing #3 asked for): each process gets the margins of its
        own rows — the rows its chunk store holds — read from its
        addressable shards of the globally-sharded per-chunk result
        (that path keeps the synchronous shard readback).  GLOBAL
        metrics over these scores reduce with one psum
        (evaluation/device.py) or an explicit allgather, never by
        materializing global rows on one host."""
        fused = self.chunk_fuse > 1
        if fused:
            n_items = len(self._groups)
            get_host = self._fused_host_item
            progs = [
                self._score if len(g) == 1 else self._score_fused(len(g))
                for g in self._groups
            ]
        else:
            n_items = self.stream.n_chunks
            get_host = self._host_item
            progs = [self._score] * n_items
        outs: list = [None] * n_items
        window = 0 if self.prefetch_depth == 1 else self.prefetch_depth
        pend: collections.deque = collections.deque()

        def materialize(j, m):
            outs[j] = np.asarray(m).reshape(-1)

        def consume(k, dev):
            m = progs[k](w, dev)
            if self._multihost:
                # Local shard blocks, in global (= process-major) order:
                # together they are exactly this process's contiguous
                # local rows of the chunk, laid out (local_shard, row).
                shards = sorted(
                    m.addressable_shards, key=lambda s: s.index[0].start
                )
                outs[k] = np.concatenate(
                    [np.asarray(s.data).reshape(-1) for s in shards]
                )
                return
            if hasattr(m, "copy_to_host_async"):
                m.copy_to_host_async()
            pend.append((k, m))
            if len(pend) > window:
                materialize(*pend.popleft())

        st_nbytes = self._staging.nbytes if self._staging else 0
        glens = [len(g) for g in self._groups] if fused else None
        run_prefetched(
            n_items, get_host, self._put, consume,
            depth=self.prefetch_depth, stats=self.transfer_stats,
            logical_nbytes=(
                (lambda k: st_nbytes * (glens[k] if glens else 1))
                if self._codec is not None else None
            ),
        )
        while pend:
            materialize(*pend.popleft())
        return np.concatenate(outs)[: self.stream.n_rows]

    def _score_fused(self, n_fused: int) -> Callable:
        key = ("score", n_fused, None, False)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        score = self._score_step

        def fused(w, chunk):
            def body(c, b):
                return c, score(w, b)

            _, ms = lax.scan(
                body, jnp.zeros((), jnp.float32), chunk, length=n_fused
            )
            return ms

        prog = jax.jit(fused)
        self._progs[key] = prog
        return prog


# ---------------------------------------------------------------------------
# Host-loop L-BFGS (the streamed outer loop)
# ---------------------------------------------------------------------------


@jax.jit
def _direction_jit(grad, S, Y, rho, gamma, n_pairs):
    return -_two_loop(grad, S, Y, rho, gamma, n_pairs)


@jax.jit
def _history_jit(S, Y, rho, gamma, n_pairs, w_new, w_old, g_new, g_old):
    return update_history(
        S, Y, rho, gamma, n_pairs, w_new - w_old, g_new - g_old
    )


@jax.jit
def _axpy_jit(w0, t, direction):
    return w0 + t * direction


@jax.jit
def _axpy_batch_jit(w0, ts, direction):
    # Row i is w0 + ts[i]·direction, elementwise — bitwise the _axpy_jit
    # result for that step (broadcasting adds no reduction or re-blocking).
    return w0[None, :] + ts[:, None] * direction[None, :]


@jax.jit
def _vdot_jit(a, b):
    return jnp.vdot(a, b)


class _HostLS:
    """Result of the host-loop weak-Wolfe search (mirrors LineSearchResult)."""

    __slots__ = ("step", "w", "value", "grad", "n_evals", "success")

    def __init__(self, step, w, value, grad, n_evals, success):
        self.step = step
        self.w = w
        self.value = value
        self.grad = grad
        self.n_evals = n_evals
        self.success = success


def _host_wolfe(vg, w0, f0, g0, direction, initial_step,
                cfg: LineSearchConfig, vg_batch=None):
    """Weak-Wolfe bisection search with host control flow — the same
    bracketing rules as optim/linesearch.wolfe_line_search, but each trial
    evaluation is a full streamed pass, so host round trips are free by
    comparison.

    With ``vg_batch`` (a (K, d) → ((K,), (K, d)) batched evaluator, e.g.
    :meth:`StreamingObjective.value_and_grad_batch`), each streamed pass
    SPECULATIVELY evaluates the current trial step plus its two possible
    bisection successors — the successor for either branch of the Armijo
    test is computable from the current bracket before the trial's result
    is known — so every pass resolves two levels of the search and the
    pass count per line search roughly halves.  The examined candidate
    sequence (and therefore the accepted step and ``n_evals``) is
    IDENTICAL to the one-trial-per-pass loop.
    """
    dg0 = float(_vdot_jit(direction, g0))
    cache: dict = {}

    def clamp(t):
        return min(max(t, cfg.min_step), cfg.max_step)

    def successors(t, lo, hi):
        # The two possible next trials after examining t with bracket
        # (lo, hi): armijo-ok moves lo up to t, armijo-fail moves hi down
        # to t — the SAME update+bisection+clamp arithmetic as the main
        # loop, so a later cache lookup hits the exact float.
        out = []
        for lo2, hi2 in ((max(lo, t), hi), (lo, min(hi, t))):
            tn = 2.0 * lo2 if math.isinf(hi2) else 0.5 * (lo2 + hi2)
            out.append(clamp(tn))
        return out

    def evaluate(t, lo, hi):
        if vg_batch is None:
            w = _axpy_jit(w0, jnp.float32(t), direction)
            f, g = vg(w)
            return w, float(f), g, float(_vdot_jit(direction, g))
        if t not in cache:
            cands = [t]
            for tn in successors(t, lo, hi):
                if tn not in cands and tn not in cache:
                    cands.append(tn)
            while len(cands) < _WOLFE_TRIAL_BATCH:
                cands.append(cands[-1])  # pad: one static batch shape
            cands = cands[:_WOLFE_TRIAL_BATCH]
            ws = _axpy_batch_jit(
                w0, jnp.asarray(cands, jnp.float32), direction
            )
            fs, gs = vg_batch(ws)
            fs_host = np.asarray(fs)
            for i, tc in enumerate(cands):
                if tc not in cache:
                    cache[tc] = (
                        ws[i], float(fs_host[i]), gs[i],
                        float(_vdot_jit(direction, gs[i])),
                    )
        return cache[t]

    t = float(initial_step)
    lo, hi = 0.0, math.inf
    w, f, g, dg = evaluate(t, lo, hi)
    n_evals = 1
    while True:
        armijo_ok = f <= f0 + cfg.c1 * t * dg0
        curvature_ok = dg >= cfg.c2 * dg0
        if armijo_ok and curvature_ok:
            break
        if n_evals >= cfg.max_evals:
            break
        if armijo_ok:
            lo = max(lo, t)
        else:
            hi = min(hi, t)
        t_next = 2.0 * lo if math.isinf(hi) else 0.5 * (lo + hi)
        t_next = clamp(t_next)
        if t_next == t or hi - lo < cfg.min_step:
            break
        t = t_next
        w, f, g, dg = evaluate(t, lo, hi)
        n_evals += 1
    success = (
        f <= f0 + cfg.c1 * t * dg0 and dg >= cfg.c2 * dg0
    )
    return _HostLS(t, w, f, g, n_evals, success)


def streaming_lbfgs_solve(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: LBFGSConfig = LBFGSConfig(),
    value_and_grad_batch=None,
) -> SolveResult:
    """L-BFGS with the outer loop on the host: ``value_and_grad`` may do
    arbitrary host work per call (stream chunks, launch many programs).

    Math mirrors optim/lbfgs.lbfgs_solve exactly — same two-loop recursion
    and curvature-history update (via the SAME functions, jitted), same
    weak-Wolfe bracketing constants, same stall rule (a failed,
    non-improving line search keeps the incumbent), same convergence tests.

    ``value_and_grad_batch``: optional (K, d) → ((K,), (K, d)) evaluator
    (:meth:`StreamingObjective.value_and_grad_batch`); when given, the
    line search batches each trial with its speculative successors so one
    streamed pass resolves ~2 trials (identical trajectory — see
    :func:`_host_wolfe`).
    """
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    w0 = jnp.asarray(w0)

    f_dev, g = value_and_grad(w0)
    f = float(f_dev)
    g_norm = float(jnp.linalg.norm(g))
    tol_scale = max(1.0, g_norm)

    values = np.full(config.max_iters + 1, np.nan, np.float64)
    gnorms = np.full(config.max_iters + 1, np.nan, np.float64)
    values[0] = f
    gnorms[0] = g_norm

    S = jnp.zeros((m, d), dtype)
    Y = jnp.zeros((m, d), dtype)
    rho = jnp.zeros((m,), dtype)
    gamma = jnp.asarray(1.0, dtype)
    n_pairs = jnp.asarray(0, jnp.int32)

    w = w0
    k = 0
    converged = g_norm <= config.tolerance * tol_scale
    while not converged and k < config.max_iters:
        direction = _direction_jit(g, S, Y, rho, gamma, n_pairs)
        dg = float(_vdot_jit(direction, g))
        if dg >= 0.0:  # non-descent from a stale history → steepest descent
            direction = -g
        first = int(n_pairs) == 0
        init_step = min(1.0, 1.0 / g_norm) if first else 1.0

        ls = _host_wolfe(
            value_and_grad, w, f, g, direction, init_step,
            config.line_search, vg_batch=value_and_grad_batch,
        )

        S, Y, rho, gamma, n_pairs = _history_jit(
            S, Y, rho, gamma, n_pairs, ls.w, w, ls.grad, g
        )

        k += 1
        rel_impr = abs(f - ls.value) / max(abs(f), 1e-12)
        stalled = (not ls.success) and ls.value >= f
        if stalled:
            # Keep the incumbent; convergence measured at the kept point
            # (mirrors the resident solver's stall rule).
            converged = g_norm <= config.tolerance * tol_scale
        else:
            w, f, g = ls.w, ls.value, ls.grad
            g_norm = float(jnp.linalg.norm(ls.grad))
            converged = (
                g_norm <= config.tolerance * tol_scale
                or rel_impr <= config.tolerance * 1e-2
            )
        values[k] = f
        gnorms[k] = g_norm
        if stalled:
            break

    return SolveResult(
        w=w,
        value=jnp.asarray(f, jnp.float32),
        grad=g,
        iterations=jnp.asarray(k, jnp.int32),
        converged=jnp.asarray(bool(converged)),
        values=jnp.asarray(values, jnp.float32),
        grad_norms=jnp.asarray(gnorms, jnp.float32),
    )


# ---------------------------------------------------------------------------
# Host-loop OWL-QN (streamed L1 / elastic-net)
# ---------------------------------------------------------------------------


@jax.jit
def _ow_pseudo_jit(w, grad, l1, mask):
    return _pseudo_gradient(w, grad, l1, mask)


@jax.jit
def _ow_dir_jit(pg, S, Y, rho, gamma, n_pairs):
    direction = -_two_loop(pg, S, Y, rho, gamma, n_pairs)
    # Orthant alignment (Andrew & Gao §3.2): zero coordinates whose sign
    # disagrees with -pg; all-zero direction degrades to steepest descent.
    direction = jnp.where(direction * (-pg) > 0, direction, 0.0)
    deg = jnp.vdot(direction, direction) == 0.0
    return jnp.where(deg, -pg, direction)


@jax.jit
def _ow_trial_jit(w, t, direction, xi):
    wt = w + t * direction
    return jnp.where(wt * xi >= 0, wt, 0.0)  # orthant projection


@jax.jit
def _ow_trials_jit(w, ts, direction, xi):
    # Row i is the _ow_trial_jit result for ts[i], elementwise (broadcast
    # only — no reductions), so the batched trials match bitwise.
    wt = w[None, :] + ts[:, None] * direction[None, :]
    return jnp.where(wt * xi[None, :] >= 0, wt, 0.0)


@jax.jit
def _ow_l1_jit(w, l1, mask):
    return l1 * jnp.vdot(mask, jnp.abs(w))


def streaming_owlqn_solve(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    l1_weight: float,
    config: OWLQNConfig = OWLQNConfig(),
    l1_mask: Optional[Array] = None,
    value_and_grad_batch=None,
) -> SolveResult:
    """OWL-QN with the outer loop on the host — the streamed counterpart
    of optim/owlqn.owlqn_solve (same pseudo-gradient, orthant alignment
    and projection, projected-step Armijo with non-strict backtracking,
    smooth-gradient history, stall rule, convergence tests).
    ``value_and_grad`` evaluates only the smooth part.

    ``value_and_grad_batch``: optional batched smooth evaluator; when
    given, each streamed pass evaluates a ladder of backtracking
    candidates ``t, tβ, tβ², …`` at once (the ladder is deterministic, so
    the examined sequence is identical to one-trial-per-pass)."""
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    w0 = jnp.asarray(w0)
    l1 = jnp.asarray(l1_weight, jnp.float32)
    mask = (
        jnp.ones((d,), dtype) if l1_mask is None
        else jnp.asarray(l1_mask, dtype)
    )

    def full_value(w, smooth) -> float:
        return float(smooth) + float(_ow_l1_jit(w, l1, mask))

    f_smooth, g = value_and_grad(w0)
    w = w0
    f = full_value(w, f_smooth)
    # The pseudo-gradient is maintained as an invariant (pg ≡ pseudo(w, g))
    # across the loop: computed once here, refreshed only on acceptance —
    # the old loop recomputed it at the top of every iteration even though
    # the accepted iteration had just evaluated the identical value.
    pg = _ow_pseudo_jit(w, g, l1, mask)
    pg_norm = float(jnp.linalg.norm(pg))
    tol_scale = max(1.0, pg_norm)

    values = np.full(config.max_iters + 1, np.nan, np.float64)
    gnorms = np.full(config.max_iters + 1, np.nan, np.float64)
    values[0] = f
    gnorms[0] = pg_norm

    S = jnp.zeros((m, d), dtype)
    Y = jnp.zeros((m, d), dtype)
    rho = jnp.zeros((m,), dtype)
    gamma = jnp.asarray(1.0, dtype)
    n_pairs = jnp.asarray(0, jnp.int32)

    k = 0
    converged = pg_norm <= config.tolerance * tol_scale
    while not converged and k < config.max_iters:
        direction = _ow_dir_jit(pg, S, Y, rho, gamma, n_pairs)
        # Orthant: sign(w) where nonzero, else the step's sign.
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
        t = (
            min(1.0, 1.0 / float(jnp.linalg.norm(pg)))
            if int(n_pairs) == 0 else 1.0
        )

        cache: dict = {}

        def trial(t):
            if value_and_grad_batch is None:
                wt = _ow_trial_jit(w, jnp.float32(t), direction, xi)
                smooth, grad = value_and_grad(wt)
                return wt, full_value(wt, smooth), grad
            if t not in cache:
                # The backtracking ladder from t, by REPEATED
                # multiplication (exactly the floats `t *= backtrack`
                # would visit — t·β**i differs bitwise).
                ts = [t]
                for _ in range(_OWLQN_TRIAL_BATCH - 1):
                    ts.append(ts[-1] * config.backtrack)
                ts = [tc for tc in ts if tc not in cache]
                while len(ts) < _OWLQN_TRIAL_BATCH:
                    ts.append(ts[-1])
                wts = _ow_trials_jit(
                    w, jnp.asarray(ts, jnp.float32), direction, xi
                )
                smooths, grads = value_and_grad_batch(wts)
                smooths_host = np.asarray(smooths)
                for i, tc in enumerate(ts):
                    if tc not in cache:
                        cache[tc] = (
                            wts[i],
                            full_value(wts[i], smooths_host[i]),
                            grads[i],
                        )
            return cache[t]

        w_new, f_new, g_new = trial(t)
        n_evals = 1
        # Armijo on the PROJECTED step, non-strict (a fully-clamped trial
        # must keep backtracking) — mirrors the resident solver.
        while (
            f_new >= f + config.armijo_c1 * float(_vdot_jit(pg, w_new - w))
            and n_evals < config.max_line_search_evals
        ):
            t *= config.backtrack
            w_new, f_new, g_new = trial(t)
            n_evals += 1

        S, Y, rho, gamma, n_pairs = _history_jit(
            S, Y, rho, gamma, n_pairs, w_new, w, g_new, g
        )

        k += 1
        rel_impr = abs(f - f_new) / max(abs(f), 1e-12)
        stalled = f_new >= f
        if stalled:
            converged = (
                float(jnp.linalg.norm(pg)) <= config.tolerance * tol_scale
            )
        else:
            w, f, g = w_new, f_new, g_new
            pg = _ow_pseudo_jit(w, g, l1, mask)
            pg_norm = float(jnp.linalg.norm(pg))
            converged = (
                pg_norm <= config.tolerance * tol_scale
                or rel_impr <= config.tolerance * 1e-2
            )
        values[k] = f
        gnorms[k] = pg_norm
        if stalled:
            break

    # pg already equals the pseudo-gradient at the returned (w, g) — the
    # invariant holds through both the acceptance and stall branches.
    return SolveResult(
        w=w,
        value=jnp.asarray(f, jnp.float32),
        grad=pg,
        iterations=jnp.asarray(k, jnp.int32),
        converged=jnp.asarray(bool(converged)),
        values=jnp.asarray(values, jnp.float32),
        grad_norms=jnp.asarray(gnorms, jnp.float32),
    )


# ---------------------------------------------------------------------------
# Host-loop TRON (streamed trust-region Newton)
# ---------------------------------------------------------------------------


def _host_steihaug_cg(hvp, g, delta, max_iters, tol):
    """Steihaug CG with host control flow — same math as optim/tron.py's
    ``_steihaug_cg`` (negative-curvature and radius-crossing exits to the
    boundary, residual kept consistent with the returned step), but each
    Hessian-vector product is a full streamed pass, so host round-trips
    are free by comparison.

    Returns ``(s, r, n_hvp)`` with ``r = -g - H·s`` for the returned ``s``
    (so sᵀHs is recoverable without another streamed pass)."""
    s = jnp.zeros_like(g)
    r = _axpy_jit(jnp.zeros_like(g), jnp.float32(-1.0), g)
    p = r
    rr = float(_vdot_jit(r, r))
    if math.sqrt(rr) <= tol:
        return s, r, 0
    n_hvp = 0
    for _ in range(max_iters):
        Hp = hvp(p)
        n_hvp += 1
        pHp = float(_vdot_jit(p, Hp))
        neg_curv = pHp <= 0.0
        alpha = rr / (pHp if pHp > 0.0 else 1.0)
        s_next = _axpy_jit(s, jnp.float32(alpha), p)
        crosses = math.sqrt(float(_vdot_jit(s_next, s_next))) >= delta
        if neg_curv or crosses:
            # Go to the trust-region boundary along p: ‖s + τp‖ = delta.
            pp = float(_vdot_jit(p, p))
            sp = float(_vdot_jit(s, p))
            ss = float(_vdot_jit(s, s))
            disc = max(sp * sp + pp * (delta * delta - ss), 0.0)
            tau = (-sp + math.sqrt(disc)) / max(pp, 1e-30)
            s = _axpy_jit(s, jnp.float32(tau), p)
            r = _axpy_jit(r, jnp.float32(-tau), Hp)
            break
        s = s_next
        r = _axpy_jit(r, jnp.float32(-alpha), Hp)
        rr_new = float(_vdot_jit(r, r))
        if math.sqrt(rr_new) <= tol:
            break
        beta = rr_new / max(rr, 1e-30)
        p = _axpy_jit(r, jnp.float32(beta), p)
        rr = rr_new
    return s, r, n_hvp


def streaming_tron_solve(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    hvp_fn: Callable[[Array, Array], Array],
    w0: Array,
    config=None,
) -> SolveResult:
    """Trust-region Newton-CG with the outer loop on the host — the
    streamed counterpart of optim/tron.tron_solve, closing the last
    optimizer×residency cell: the reference runs TRON distributed, one
    ``HessianVectorAggregator`` treeAggregate round per CG step
    (SURVEY.md §3.1 / BASELINE config 3); here each CG step is one
    streamed :meth:`StreamingObjective.hvp` pass.

    Math mirrors the resident solver step-for-step: LIBLINEAR initial
    radius ``‖g0‖``, the same forcing tolerance, acceptance threshold and
    radius-update constants (via the shared ``TRONConfig``), the same
    boundary-consistent residual trick recovering sᵀHs without an extra
    HVP, and the same convergence/stall rules — so a single-chunk streamed
    solve tracks the resident trajectory to float tolerance.

    ``hvp_fn(w, v)`` must return the REGULARIZED Hessian-vector product.
    """
    from photon_ml_tpu.optim.tron import TRONConfig

    if config is None:
        config = TRONConfig()
    w = jnp.asarray(w0)

    f_dev, g = value_and_grad(w)
    f = float(f_dev)
    g_norm = float(jnp.linalg.norm(g))
    tol_scale = max(1.0, g_norm)
    delta = g_norm  # LIBLINEAR: initial radius = ||g0||

    values = np.full(config.max_iters + 1, np.nan, np.float64)
    gnorms = np.full(config.max_iters + 1, np.nan, np.float64)
    values[0] = f
    gnorms[0] = g_norm

    k = 0
    converged = g_norm <= config.tolerance * tol_scale
    while not converged and k < config.max_iters:
        cg_tol = config.cg_tol * g_norm
        step, residual, _ = _host_steihaug_cg(
            lambda v: hvp_fn(w, v), g, delta, config.max_cg_iters, cg_tol
        )

        w_try = _axpy_jit(w, jnp.float32(1.0), step)
        f_try_dev, g_try = value_and_grad(w_try)
        f_try = float(f_try_dev)

        gs = float(_vdot_jit(g, step))
        # r = -g - H·s  ⇒  sᵀHs = -s·r - s·g (one saved streamed pass per
        # outer iteration, as in the resident solver).
        sHs = -float(_vdot_jit(step, residual)) - gs
        pred = -(gs + 0.5 * sHs)
        ared = f - f_try
        rho = ared / (pred if pred > 0.0 else 1e-30)
        accept = rho > config.eta0 and pred > 0.0

        # Radius update (LIBLINEAR-style, same constants as the resident).
        snorm = math.sqrt(max(float(_vdot_jit(step, step)), 0.0))
        if rho < config.eta1:
            delta_new = max(config.sigma1 * snorm, config.sigma2 * delta)
            if rho < config.eta0:
                delta_new *= config.sigma2
        elif rho > config.eta2:
            delta_new = max(delta, config.sigma3 * snorm)
        else:
            delta_new = delta
        delta = max(delta_new, 1e-20)

        k += 1
        if accept:
            rel_impr = abs(ared) / max(abs(f), 1e-12)
            w, f, g = w_try, f_try, g_try
            g_norm = float(jnp.linalg.norm(g))
        else:
            rel_impr = math.inf
        converged = (
            g_norm <= config.tolerance * tol_scale
            or rel_impr <= config.tolerance * 1e-2
        )
        values[k] = f
        gnorms[k] = g_norm
        if delta <= 1e-18:  # radius collapsed: no further progress possible
            break

    return SolveResult(
        w=w,
        value=jnp.asarray(f, jnp.float32),
        grad=g,
        iterations=jnp.asarray(k, jnp.int32),
        converged=jnp.asarray(bool(converged)),
        values=jnp.asarray(values, jnp.float32),
        grad_norms=jnp.asarray(gnorms, jnp.float32),
    )


# ---------------------------------------------------------------------------
# Grid sweep over a streamed dataset
# ---------------------------------------------------------------------------


#: The solvers with a streamed pass loop (``OptimizerConfig.solver`` names).
STREAMED_SOLVERS = ("lbfgs", "owlqn", "tron")


def ensure_streamable(config) -> None:
    """Reject configs the streamed path cannot train — callable BEFORE the
    (possibly hours-long) chunk-store ingest, and always re-checked by
    :func:`streaming_run_grid`.

    Every optimizer now streams (L-BFGS, OWL-QN, and smooth TRON via
    :func:`streaming_tron_solve`), so this currently accepts everything;
    it remains the single gate future unstreamable features must fail
    loudly through."""


def streaming_run_grid(
    problem,
    stream: StreamingGlmData,
    reg_weights: Sequence[float],
    w0: Optional[Array] = None,
    mesh=None,
    warm_start: bool = True,
    solved: Optional[dict] = None,
    on_solved=None,
    accumulate: str = "f32",
    l1_mask: Optional[Array] = None,
    prefetch_depth: int = 2,
    chunk_fuse: int = 1,
    batch_linesearch: bool = True,
    compress: str = "off",
    hot_budget_bytes: int = 0,
):
    """The λ-grid warm-start chain (optim.problem.grid_loop) over a
    streamed dataset.  L1/elastic-net routes to the streamed OWL-QN and
    smooth TRON to the streamed trust-region solver (exactly like the
    resident problem.solve's static routing).

    ``chunk_fuse``: chunks folded per device dispatch (``lax.scan``) —
    amortizes per-dispatch overhead for small chunks; ``batch_linesearch``
    evaluates a bracket of line-search candidates per streamed pass
    (identical trial sequence, ~half the passes).  ``compress`` and
    ``hot_budget_bytes`` are the transfer-avoidance knobs (compressed
    wire formats + importance-aware HBM working set — see
    :class:`StreamingObjective`); lossless compression and the cache
    leave every solve bitwise unchanged.
    """
    from photon_ml_tpu.optim.problem import choose_solver
    from photon_ml_tpu.optim.tron import TRONConfig

    cfg = problem.config
    ensure_streamable(cfg)
    sobj = StreamingObjective(
        problem.objective, stream, mesh=mesh, accumulate=accumulate,
        prefetch_depth=prefetch_depth, chunk_fuse=chunk_fuse,
        compress=compress, hot_budget_bytes=hot_budget_bytes,
    )
    opt = cfg.optimizer
    l1_frac = cfg.regularization.l1_weight(1.0)
    name = choose_solver(opt, l1_frac=l1_frac)
    if name not in STREAMED_SOLVERS:
        raise ValueError(
            f"solver {name!r} has no streamed implementation; the "
            "streamed grid serves the solvers with a streamed pass "
            f"loop {STREAMED_SOLVERS} — distributed solvers run over "
            "sharded resident data (solvers.sharded.run_grid_sharded)"
        )

    def solve_fn(lam, w_prev):
        l1 = l1_frac * float(lam)
        l2 = cfg.regularization.l2_weight(1.0) * float(lam)
        if w_prev is None:
            w_prev = jnp.zeros((stream.n_features,), jnp.float32)
        vg = lambda w: sobj.value_and_grad(w, l2)
        vgb = (
            (lambda ws: sobj.value_and_grad_batch(ws, l2))
            if batch_linesearch else None
        )
        if name == "lbfgs":
            return streaming_lbfgs_solve(
                vg,
                w_prev,
                LBFGSConfig(
                    max_iters=opt.max_iters,
                    tolerance=opt.tolerance,
                    history=opt.history,
                ),
                value_and_grad_batch=vgb,
            )
        if name == "owlqn":
            return streaming_owlqn_solve(
                vg,
                w_prev,
                l1,
                OWLQNConfig(
                    max_iters=opt.max_iters,
                    tolerance=opt.tolerance,
                    history=opt.history,
                ),
                l1_mask=l1_mask,
                value_and_grad_batch=vgb,
            )
        return streaming_tron_solve(
            vg,
            lambda w, v: sobj.hvp(w, v, l2),
            w_prev,
            TRONConfig(max_iters=opt.max_iters, tolerance=opt.tolerance),
        )

    variance_fn = None
    if cfg.compute_variances:
        def variance_fn(w, lam):
            l2 = cfg.regularization.l2_weight(1.0) * float(lam)
            diag = sobj.hessian_diagonal(w)
            return 1.0 / jnp.maximum(diag + l2, 1e-12)

    return problem.grid_loop(
        solve_fn, reg_weights, w0, warm_start, solved, on_solved, variance_fn
    )
