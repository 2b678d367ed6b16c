"""Bounded-depth background prefetch for host→device chunk streams.

The synchronous pattern (``device_put`` then step, inline in the consume
loop) leaves every host-side cost — packing, slicing, dispatch syscalls,
multihost local-block assembly — on the critical path between two device
programs.  This module moves all of it off that path, as a three-stage
software pipeline (the classic latency-hiding shape from the TPU
performance literature — double buffering generalized to a bounded
window):

    pack thread:      get_item(k) ──bounded hand-off queue──►
    transfer thread:  put(item) → [transfer timed to completion] ──►
    caller thread:    queue → consume(k, dev) → release permit

Pack and transfer are SEPARATE threads: chunk k+1's host-side
materialization (staging-buffer stacking, memmap paging, multihost
assembly) runs while chunk k's bytes are still crossing the link — the
transfer thread, not the packer, waits on the transferred array's
readiness, so the link and the host-side copy machinery stay busy
simultaneously.  A bounded hand-off queue (``depth`` items) keeps the
packer from running arbitrarily ahead of the link (host RAM for packed
items stays O(depth)).

A semaphore of ``depth`` permits bounds how many device items are live
(transferred or transferring, not yet consumed): ``depth=2`` is the
classic double buffer (chunk k+1 moves while chunk k computes, ≤2 chunks
in HBM), ``depth=1`` degrades to serial transfer/compute (the
measurement baseline), larger depths absorb jittery transports.  A
permit is released only after ``consume`` returns — consumers that sync
on their results bound actual HBM residency, not just Python references
(the streamed accumulators sync on a bounded window of carries:
optim/streaming.py).

Every transfer is timed to completion on the transfer thread, so
:class:`TransferStats` reports ACHIEVED bytes/second, not dispatch rate
— a timing that ends before the transfer does measures the enqueue
(docs/performance.md "Measurement").  The stats attribute wall time
to STAGES so a regression names the guilty one: ``pack_seconds`` (host
materialization), ``dispatch_seconds`` (the ``put`` call itself, i.e.
Python/runtime dispatch — a subset of ``h2d_seconds``), ``h2d_seconds``
(dispatch through transfer completion) and ``consume_seconds`` (the
caller's per-item compute dispatch + syncs).  When the pipeline
overlaps, the summed stage seconds EXCEED the pass's wall time.  Stall
counters tell the two
failure stories apart: ``consumer_stalls`` (compute waited on the
queue: the stream is ingest-bound) vs
``producer_stalls`` (transfers waited on compute: the link is keeping
up and further h2d work is pointless).
"""

from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time
from typing import Callable

import jax

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.analysis import sanitizers
from photon_ml_tpu.chaos import core as chaos_mod

#: how long the caller waits for the background threads after a pass (a
#: healthy pipeline joins in microseconds — this bounds a WEDGED thread).
#: Module-level so tests can shrink it without patching call sites.
JOIN_TIMEOUT_SECONDS = 30.0


@dataclasses.dataclass
class TransferStats:
    """Cumulative host→device transfer observability for one stream.

    Aggregated across passes (``reset()`` between measurement windows);
    ``gbps``/``chunk_seconds`` derive the headline rates and the
    ``*_seconds`` fields attribute wall time per pipeline stage.
    """

    chunks: int = 0  # transfers completed
    bytes: int = 0  # WIRE bytes moved (what actually crossed the link)
    logical_bytes: int = 0  # decoded bytes those transfers stand for
    pack_seconds: float = 0.0  # summed get_item wall (pack stage)
    dispatch_seconds: float = 0.0  # summed put() call wall (⊂ h2d_seconds)
    h2d_seconds: float = 0.0  # summed per-transfer wall time (to completion)
    consume_seconds: float = 0.0  # summed consume() wall (compute stage)
    producer_stalls: int = 0  # transfer waited for a free permit (healthy)
    producer_stall_seconds: float = 0.0
    consumer_stalls: int = 0  # compute waited for a transfer (ingest-bound)
    consumer_stall_seconds: float = 0.0
    passes: int = 0  # completed pipeline runs
    max_live: int = 0  # high-water of concurrently-live device items
    max_live_bytes: int = 0  # high-water of live device BYTES (HBM bound)

    @property
    def gbps(self) -> float:
        """Achieved h2d rate over everything recorded, GB/s — WIRE
        bytes, so this stays an honest link measurement even when the
        stream is compressed."""
        return (
            self.bytes / self.h2d_seconds / 1e9 if self.h2d_seconds else 0.0
        )

    @property
    def compression_ratio(self) -> float:
        """logical/wire bytes over everything recorded (1.0 = raw)."""
        return self.logical_bytes / self.bytes if self.bytes else 1.0

    @property
    def chunk_seconds(self) -> float:
        """Mean per-chunk transfer wall time."""
        return self.h2d_seconds / self.chunks if self.chunks else 0.0

    @property
    def stage_seconds(self) -> float:
        """Summed wall across the three pipeline stages (pack + transfer
        + compute).  When this exceeds a pass's wall-clock time, the
        stages overlapped.  ``dispatch_seconds`` is a subset of ``h2d_seconds``
        and is NOT double-counted here."""
        return self.pack_seconds + self.h2d_seconds + self.consume_seconds

    def snapshot(self) -> dict:
        d = dataclasses.asdict(self)
        d["gbps"] = self.gbps
        d["chunk_seconds"] = self.chunk_seconds
        d["stage_seconds"] = self.stage_seconds
        d["compression_ratio"] = self.compression_ratio
        return d

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, f.default)


class _ProducerFailure:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _publish_pass(
    stats: TransferStats, before: tuple, run_max: int,
    run_max_bytes: int = 0,
) -> None:
    """Feed this pass's TransferStats DELTAS into the process telemetry
    registry (PR 1 left the stats a dead-end dataclass unless a caller
    printed them).  Counters accumulate correctly across every stream in
    the process because each pass contributes only its own delta; gauges
    carry the LAST pass's achieved rates.  One call per pass — nothing
    here runs per chunk."""
    tel = telemetry_mod.current()
    if not tel.enabled:
        return
    (bytes0, h2d0, chunks0, cs0, css0, ps0, pss0,
     pack0, disp0, cons0, logical0) = before
    d_bytes = stats.bytes - bytes0
    d_logical = stats.logical_bytes - logical0
    d_h2d = stats.h2d_seconds - h2d0
    d_chunks = stats.chunks - chunks0
    d_pack = stats.pack_seconds - pack0
    d_disp = stats.dispatch_seconds - disp0
    d_cons = stats.consume_seconds - cons0
    tel.counter("h2d_bytes_total").inc(d_bytes)
    # Wire vs logical split (compressed chunk formats): h2d_bytes_total
    # and h2d_gbps stay WIRE-denominated — the honest link measurement —
    # while the stream_* pair lets dashboards derive the encoding's win.
    tel.counter("stream_wire_bytes_total").inc(d_bytes)
    tel.counter("stream_logical_bytes_total").inc(d_logical)
    tel.counter("h2d_chunks_total").inc(d_chunks)
    tel.counter("h2d_seconds").inc(d_h2d)
    tel.counter("prefetch_pack_seconds").inc(d_pack)
    tel.counter("prefetch_dispatch_seconds").inc(d_disp)
    tel.counter("prefetch_consume_seconds").inc(d_cons)
    tel.counter("consumer_stalls").inc(stats.consumer_stalls - cs0)
    tel.counter("consumer_stall_seconds").inc(
        stats.consumer_stall_seconds - css0
    )
    tel.counter("producer_stalls").inc(stats.producer_stalls - ps0)
    tel.counter("producer_stall_seconds").inc(
        stats.producer_stall_seconds - pss0
    )
    tel.counter("prefetch_passes").inc()
    if d_h2d > 0.0:
        tel.gauge("h2d_gbps").set(d_bytes / d_h2d / 1e9)
    if d_bytes > 0:
        tel.gauge("stream_compression_ratio").set(d_logical / d_bytes)
    if d_chunks > 0:
        tel.gauge("h2d_chunk_seconds").set(d_h2d / d_chunks)
        tel.gauge("prefetch_pack_chunk_seconds").set(d_pack / d_chunks)
        tel.gauge("prefetch_dispatch_chunk_seconds").set(d_disp / d_chunks)
        tel.gauge("prefetch_consume_chunk_seconds").set(d_cons / d_chunks)
    tel.gauge("prefetch_max_live").set(run_max)
    # HBM accounting (ROADMAP item 1's measurement foundation): the
    # pass's high-water of transferred-not-yet-consumed device bytes —
    # what the depth bound actually pinned, in bytes rather than items.
    tel.gauge("hbm_live_peak_bytes").set(run_max_bytes)
    tel.event(
        "prefetch.pass",
        chunks=d_chunks,
        bytes=d_bytes,
        h2d_seconds=round(d_h2d, 6),
        pack_seconds=round(d_pack, 6),
        dispatch_seconds=round(d_disp, 6),
        consume_seconds=round(d_cons, 6),
        logical_bytes=d_logical,
        consumer_stalls=stats.consumer_stalls - cs0,
        producer_stalls=stats.producer_stalls - ps0,
        max_live=run_max,
        max_live_bytes=run_max_bytes,
    )


def run_prefetched(
    n_items: int,
    get_item: Callable[[int], object],
    put: Callable[[object], object],
    consume: Callable[[int, object], None],
    depth: int = 2,
    stats: TransferStats | None = None,
    logical_nbytes: Callable[[int], int] | None = None,
) -> int:
    """Stream ``n_items`` through a bounded-depth three-stage pipeline.

    ``get_item(k)`` (pack thread) materializes the host item — packing,
    slicing, stacking, memmap paging all overlap BOTH the link and
    device compute here.  ``put(item)`` (transfer thread) dispatches it
    to the device; the transfer thread — never the packer — waits for
    the transfer to complete, both for honest timing and so ``depth``
    bounds bytes in flight.  ``consume(k, dev)`` (caller thread) runs
    the item's compute; items arrive strictly in order.  Returns this
    run's high-water of live device items (≤ ``depth`` by construction).

    Pack/transfer/consume wall times land in ``stats`` per stage (see
    :class:`TransferStats`).  Pack or transfer exceptions re-raise on
    the caller thread at the failed item's position; a consumer
    exception aborts both background threads promptly (their blocking
    waits poll an abort flag).

    ``logical_nbytes(k)`` — when the host items are COMPRESSED wire
    buffers — reports the decoded bytes item ``k`` stands for, so
    ``stats`` can split wire (``bytes``) from logical
    (``logical_bytes``) transfer accounting.  Defaults to the measured
    wire bytes (ratio 1.0) for uncompressed streams.
    """
    if depth < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")
    if stats is None:
        stats = TransferStats()
    if n_items == 0:
        stats.passes += 1
        return 0
    stats_before = (
        stats.bytes, stats.h2d_seconds, stats.chunks,
        stats.consumer_stalls, stats.consumer_stall_seconds,
        stats.producer_stalls, stats.producer_stall_seconds,
        stats.pack_seconds, stats.dispatch_seconds, stats.consume_seconds,
        stats.logical_bytes,
    )

    handoff: queue.Queue = queue.Queue(maxsize=depth)
    q: queue.Queue = queue.Queue()
    permits = threading.Semaphore(depth)
    abort = threading.Event()
    live_lock = sanitizers.tracked(threading.Lock(), "prefetch.live")
    live = 0
    live_bytes = 0
    run_max = 0
    run_max_bytes = 0
    # HBM accounting gauges, resolved ONCE per pass (no-op metrics when
    # the hub is disabled, so the per-chunk cost stays one locked set):
    # live device bytes this pipeline currently pins, and how full the
    # prefetch ring is (1.0 = transfers are keeping `depth` items ahead).
    tel = telemetry_mod.current()
    ctx = tel.current_context()
    g_live = tel.gauge("hbm_live_bytes")
    g_occ = tel.gauge("prefetch_ring_occupancy_ratio")

    def _bump(delta: int, nbytes: int) -> None:
        nonlocal live, live_bytes, run_max, run_max_bytes
        with live_lock:
            live += delta
            live_bytes += nbytes
            run_max = max(run_max, live)
            run_max_bytes = max(run_max_bytes, live_bytes)
            lb, occ = live_bytes, live / depth
        g_live.set(lb)
        g_occ.set(occ)

    def _handoff_put(item) -> bool:
        while not abort.is_set():
            try:
                handoff.put(item, timeout=0.05)
                return True
            except queue.Full:
                pass
        return False

    def _packer() -> None:
        # Stage 1: host materialization only — no device calls, so a slow
        # pack never gates the link and a slow link never gates the pack
        # (up to the hand-off bound).  The attached trace context parents
        # this thread's per-pass span under the caller's span, so the
        # Perfetto view nests the pack track inside the streamed solve.
        try:
            with tel.attach(ctx), tel.span(
                "prefetch.pack_stage", items=n_items
            ):
                for k in range(n_items):
                    if abort.is_set():
                        return
                    chaos_mod.maybe_fail("prefetch.pack", item=k)
                    t0 = time.perf_counter()
                    host = get_item(k)
                    stats.pack_seconds += time.perf_counter() - t0
                    nbytes = sum(
                        leaf.nbytes
                        for leaf in jax.tree_util.tree_leaves(host)
                        if hasattr(leaf, "nbytes")
                    )
                    lb = logical_nbytes(k) if logical_nbytes else nbytes
                    if not _handoff_put((k, host, nbytes, lb)):
                        return
                    del host
        except BaseException as exc:  # surfaced on the caller thread
            # In order: the failure rides the hand-off queue behind the
            # items that packed successfully, so the consumer sees items
            # 0..k-1 and then the exception at position k.
            _handoff_put(_ProducerFailure(exc))

    def _transfer() -> None:
        # Stage 2: device dispatch + transfer completion.  Timing waits
        # on the transferred arrays' readiness happen HERE, where they
        # block nobody but the (already link-bound) transfer stream.
        try:
            with tel.attach(ctx), tel.span(
                "prefetch.transfer_stage", items=n_items
            ):
                for _ in range(n_items):
                    item = None
                    while not abort.is_set():
                        try:
                            item = handoff.get(timeout=0.05)
                            break
                        except queue.Empty:
                            pass
                    if item is None:
                        return
                    if isinstance(item, _ProducerFailure):
                        q.put(item)
                        return
                    k, host, nbytes, lb = item
                    if not permits.acquire(blocking=False):
                        t0 = time.perf_counter()
                        while not permits.acquire(timeout=0.05):
                            if abort.is_set():
                                return
                        stats.producer_stalls += 1
                        stats.producer_stall_seconds += (
                            time.perf_counter() - t0
                        )
                    if abort.is_set():
                        return
                    chaos_mod.maybe_fail("prefetch.transfer", item=k)
                    t0 = time.perf_counter()
                    dev = put(host)
                    stats.dispatch_seconds += time.perf_counter() - t0
                    for leaf in jax.tree_util.tree_leaves(dev):
                        if hasattr(leaf, "block_until_ready"):
                            leaf.block_until_ready()
                    stats.h2d_seconds += time.perf_counter() - t0
                    stats.bytes += nbytes
                    stats.logical_bytes += lb
                    stats.chunks += 1
                    _bump(+1, nbytes)
                    q.put((k, dev, nbytes))
                    del dev, host, item
        except BaseException as exc:  # surfaced on the caller thread
            q.put(_ProducerFailure(exc))

    packer = threading.Thread(target=_packer, name="h2d-pack", daemon=True)
    transfer = threading.Thread(
        target=_transfer, name="h2d-prefetch", daemon=True
    )
    packer.start()
    transfer.start()
    try:
        for _ in range(n_items):
            if q.empty():
                t0 = time.perf_counter()
                item = q.get()
                stats.consumer_stalls += 1
                stats.consumer_stall_seconds += time.perf_counter() - t0
            else:
                item = q.get()
            if isinstance(item, _ProducerFailure):
                raise item.exc
            k, dev, nbytes = item
            t0 = time.perf_counter()
            consume(k, dev)
            stats.consume_seconds += time.perf_counter() - t0
            # Drop the device reference BEFORE releasing the permit: the
            # permit accounting is the HBM bound, and a live reference
            # here would let a freed permit admit chunk k+depth while
            # chunk k's buffer still cannot be collected.
            del dev, item
            _bump(-1, -nbytes)
            permits.release()
    except BaseException:
        abort.set()
        raise
    finally:
        packer.join(timeout=JOIN_TIMEOUT_SECONDS)
        transfer.join(timeout=JOIN_TIMEOUT_SECONDS)
        leaked = [t.name for t in (packer, transfer) if t.is_alive()]
        if leaked:
            # A wedged daemon thread outliving its pass is a leak — it
            # pins chunk buffers and (on the transfer thread) the device
            # transport.  Returning normally here used to hide that
            # entirely; now it is counted, and raised when this pass was
            # otherwise about to succeed (an already-propagating failure
            # keeps priority — the count still records the leak).
            tel.counter("prefetch_thread_leak").inc(len(leaked))
            tel.event("prefetch.thread_leak", threads=leaked)
            if sys.exc_info()[0] is None:
                raise RuntimeError(
                    f"prefetch pipeline thread(s) {leaked} still alive "
                    f"after join(timeout={JOIN_TIMEOUT_SECONDS}s): a "
                    "wedged daemon thread leaked — its blocking call "
                    "(get_item/put/transfer wait) never returned; the "
                    "pass's results cannot be trusted to be complete"
                )
        while True:  # drop any queued device refs deterministically
            try:
                q.get_nowait()
            except queue.Empty:
                break
    stats.passes += 1
    stats.max_live = max(stats.max_live, run_max)
    stats.max_live_bytes = max(stats.max_live_bytes, run_max_bytes)
    _publish_pass(stats, stats_before, run_max, run_max_bytes)
    return run_max
