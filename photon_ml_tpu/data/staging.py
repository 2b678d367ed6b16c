"""Leaf coalescing: one staging buffer per dtype instead of a pytree of
small transfers.

A chunk of streamed GLM data is a pytree of dozens of numpy leaves (the
tiled Pallas layout alone carries slot codes, values, spill triples,
dense stripes and permutation maps).  Moving it with one ``device_put``
per leaf pays the transport's fixed per-transfer cost dozens of times per
chunk, and small transfers run far below the link rate.  Snap ML
(arXiv:1803.06333) gets its out-of-core GLM throughput from exactly one
discipline: chunks cross tiers as large contiguous staging buffers.

This module is that discipline for the chunk store:

- :func:`plan_staging` maps a chunk's leaves onto a few dtype-segregated
  contiguous buffers (one per distinct leaf dtype, each shaped
  ``(n_shards, elems)`` so mesh placement shards the buffer exactly like
  the leaves it carries);
- :func:`pack_chunk` fills those buffers from a chunk's leaves (host
  side, at store-build time);
- :func:`chunk_view` rebuilds the chunk as ZERO-COPY numpy views into
  the buffers, so the host-resident store costs no extra RAM and every
  existing host-side consumer (weight sums, offset scans, tests) keeps
  reading plain leaf arrays;
- :func:`unpack_device` is the compiled on-device inverse — pure
  slice + reshape, traced INTO the per-chunk program so the restored
  ``GlmData`` view costs no extra dispatch and no host round trip.

The transfer layer then moves a chunk as ``len(buffers)`` large
``device_put`` calls (typically 1-3) instead of ``len(leaves)`` small
ones.

**Compressed chunk formats** (ROADMAP item 1's transfer-avoidance half)
ride the same discipline one level down: :func:`plan_compression` scans
a staged store once and assigns every staging SLOT (one pytree leaf's
segment) an opt-in wire encoding — delta/downcast narrowing for index
blocks, bitmaps for {0,1}-valued float segments (f32, f64 and bf16),
an f32 wire for f64 blocks whose every value round-trips bitwise,
fp16/int8 quantization with per-shard scale sidecars — then re-segregates
the encoded slots into wire buffers by WIRE dtype, so a compressed chunk
still crosses as a few large contiguous transfers.  The decode
(:meth:`ChunkCodec.unpack_device`) is pure slice/cast/cumsum/shift
arithmetic traced INTO the per-chunk program exactly like the plain
unpack, so dequantization costs no extra dispatch and the f32 compute
path downstream is unchanged.  Lossless encodings (delta, downcast,
bitmap) reconstruct the device arrays BITWISE; fp16/int8 are lossy and
opt-in per mode.  The spirit is XGBoost's quantized ELLPACK pages
(arXiv:1806.11248): ship a compact encoding, decode next to the compute.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class _LeafSlot:
    """Where one pytree leaf lives inside the staging buffers."""

    buffer: int  # index into the dtype-segregated buffer list
    offset: int  # element offset within one shard's row of that buffer
    size: int  # elements per shard row
    shape: tuple  # full host leaf shape
    shard_shape: tuple  # per-shard shape (== shape when n_shards == 1)


@dataclasses.dataclass(frozen=True)
class ChunkStaging:
    """The staging-buffer layout shared by every chunk of one store.

    Buffers are dtype-segregated: mixing dtypes in one byte buffer would
    either force per-leaf bitcasts on device or break alignment for
    sub-word dtypes (the Pallas int16 slot codes).  A chunk store has a
    handful of distinct dtypes, so the transfer count stays O(1).
    """

    treedef: Any  # pytree structure (meta fields ride along untransferred)
    dtypes: tuple  # per-buffer numpy dtype
    row_elems: tuple  # per-buffer elements per shard row
    slots: tuple  # _LeafSlot per leaf, in tree_flatten order
    n_shards: int

    @property
    def n_buffers(self) -> int:
        return len(self.dtypes)

    @property
    def nbytes(self) -> int:
        """Staged bytes one chunk occupies (= bytes per chunk transfer)."""
        return sum(
            self.n_shards * r * np.dtype(dt).itemsize
            for r, dt in zip(self.row_elems, self.dtypes)
        )

    def pack(self, chunk) -> tuple:
        return pack_chunk(self, chunk)

    def view(self, buffers: Sequence[np.ndarray], treedef=None):
        return chunk_view(self, buffers, treedef)

    def unpack_device(self, buffers):
        return unpack_device(self, buffers)


def _shard_split(shape: tuple, n_shards: int) -> tuple:
    """Per-shard shape of a leaf.  With ``n_shards > 1`` every chunk leaf
    carries the leading shard axis (data/streaming.py's stacked layout)."""
    if n_shards == 1:
        return shape
    if not shape or shape[0] != n_shards:
        raise ValueError(
            f"sharded chunk leaf has shape {shape}; expected leading "
            f"shard axis of {n_shards}"
        )
    return shape[1:]


def plan_staging(chunk, n_shards: int = 1) -> ChunkStaging:
    """Lay the chunk's leaves out over dtype-segregated staging buffers.

    Every chunk of a store shares one plan (the store uniformizes shapes
    at build time); :func:`pack_chunk` enforces that.
    """
    leaves, treedef = jax.tree_util.tree_flatten(chunk)
    dtypes: list = []
    row_elems: list = []
    slots: list = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        shard_shape = _shard_split(arr.shape, n_shards)
        size = int(math.prod(shard_shape))
        dt = arr.dtype
        if dt not in dtypes:
            dtypes.append(dt)
            row_elems.append(0)
        b = dtypes.index(dt)
        slots.append(
            _LeafSlot(
                buffer=b,
                offset=row_elems[b],
                size=size,
                shape=tuple(arr.shape),
                shard_shape=tuple(shard_shape),
            )
        )
        row_elems[b] += size
    return ChunkStaging(
        treedef=treedef,
        dtypes=tuple(dtypes),
        row_elems=tuple(row_elems),
        slots=tuple(slots),
        n_shards=n_shards,
    )


def pack_chunk(staging: ChunkStaging, chunk) -> tuple:
    """Copy a chunk's leaves into freshly-allocated staging buffers.

    Returns one contiguous ``(n_shards, row_elems)`` array per dtype.
    Memmap leaves are paged in transiently (one chunk of RAM), which is
    exactly the disk-backed build's stated peak.
    """
    leaves, treedef = jax.tree_util.tree_flatten(chunk)
    if treedef != staging.treedef:
        raise ValueError(
            "chunk pytree structure does not match the staging plan "
            f"({treedef} vs {staging.treedef})"
        )
    bufs = [
        np.empty((staging.n_shards, r), dt)
        for r, dt in zip(staging.row_elems, staging.dtypes)
    ]
    for leaf, slot in zip(leaves, staging.slots):
        arr = np.asarray(leaf)
        if tuple(arr.shape) != slot.shape or arr.dtype != staging.dtypes[slot.buffer]:
            raise ValueError(
                f"chunk leaf {arr.shape}/{arr.dtype} does not match the "
                f"staging plan's {slot.shape}/"
                f"{staging.dtypes[slot.buffer]} — chunks must be "
                "uniformized before staging"
            )
        dst = bufs[slot.buffer][:, slot.offset : slot.offset + slot.size]
        dst[...] = np.ascontiguousarray(arr).reshape(
            staging.n_shards, slot.size
        )
    return tuple(bufs)


def chunk_view(staging: ChunkStaging, buffers: Sequence[np.ndarray],
               treedef=None):
    """Rebuild the chunk as zero-copy views into the staging buffers.

    ``treedef`` defaults to the plan's; pass the chunk's OWN treedef when
    per-chunk metadata must survive (the Pallas ``host_coo`` cold-path
    triples are pytree META — structurally equal across chunks but
    content-distinct, and the host-side view must keep each chunk's own).
    """
    leaves = []
    for slot in staging.slots:
        seg = buffers[slot.buffer][:, slot.offset : slot.offset + slot.size]
        leaves.append(seg.reshape(slot.shape))
    return jax.tree_util.tree_unflatten(
        staging.treedef if treedef is None else treedef, leaves
    )


def unpack_device(staging: ChunkStaging, buffers):
    """The compiled on-device unpack: slice + reshape only, traced into
    the per-chunk program.

    Works on the full ``(n_shards, row)`` buffers AND on the ``(1, row)``
    per-device blocks seen inside ``shard_map`` — the leading dim is read
    off the traced buffer, so one definition serves both contexts.
    """
    import jax.numpy as jnp  # noqa: F401  (kept local: host module import)
    from jax import lax

    leaves = []
    for slot in staging.slots:
        buf = buffers[slot.buffer]
        seg = lax.slice_in_dim(
            buf, slot.offset, slot.offset + slot.size, axis=1
        )
        if staging.n_shards == 1:
            leaves.append(seg.reshape(slot.shape))
        else:
            leaves.append(seg.reshape((buf.shape[0],) + slot.shard_shape))
    return jax.tree_util.tree_unflatten(staging.treedef, leaves)


# ---------------------------------------------------------------------------
# Compressed chunk formats: per-slot wire encodings + on-device decode
# ---------------------------------------------------------------------------

#: the ``compress`` knob's values.  "lossless" applies only encodings
#: whose device decode reconstructs the uncompressed arrays BITWISE
#: (delta / integer downcast / {0,1} bitmaps for f32, f64 and bf16 /
#: the f64-over-f32-wire downcast when every value round-trips); "fp16"
#: and "int8"
#: additionally quantize float32 segments (lossy, bounded error — see
#: tests/test_staging.py), keeping the lossless integer encodings.
COMPRESSION_MODES = ("off", "lossless", "fp16", "int8")

#: encodings whose decode is exact (bitwise) on the canonical device
#: dtype; everything else is lossy quantization.
_LOSSLESS_KINDS = frozenset({"raw", "downcast", "delta", "bitmap"})

#: narrowing ladders, same signedness as the original dtype (delta wire
#: values can be negative, so unsigned originals only ever downcast).
_SIGNED_LADDER = (np.int8, np.int16, np.int32)
_UNSIGNED_LADDER = (np.uint8, np.uint16, np.uint32)


@dataclasses.dataclass(frozen=True)
class _SlotEncoding:
    """How one staging slot crosses the wire."""

    kind: str  # raw | downcast | delta | bitmap | fp16 | int8
    wire_buffer: int  # index into the codec's wire buffer list
    wire_offset: int  # element offset within one shard's wire row
    wire_size: int  # wire elements per shard row (bitmap: packed bytes)
    scale_index: int = -1  # int8 only: column in the scale sidecar


@dataclasses.dataclass(frozen=True)
class ChunkCodec:
    """The wire format shared by every chunk of one compressed store.

    Like :class:`ChunkStaging`, one codec serves all chunks (encodings
    are chosen so every chunk's values fit — :func:`plan_compression`
    scans the whole store), so ONE compiled decode+unpack program runs
    per chunk.  Per-chunk data (int8 scales) rides inside the float32
    wire buffer as a fixed-offset sidecar, never as a separate transfer
    — on transports where the fixed per-transfer cost dominates, an
    extra tiny ``device_put`` per chunk would eat the encoding's win.
    """

    staging: ChunkStaging  # the LOGICAL layout being encoded
    mode: str
    encodings: tuple  # _SlotEncoding per slot, in staging.slots order
    wire_dtypes: tuple  # per wire buffer
    wire_row_elems: tuple  # per wire buffer, elements per shard row
    n_scales: int  # int8-quantized slot count (sidecar width)
    scale_buffer: int = -1  # wire buffer holding the scale sidecar
    scale_offset: int = 0

    @property
    def n_buffers(self) -> int:
        return len(self.wire_dtypes)

    @property
    def logical_nbytes(self) -> int:
        """Decoded (f32-path) bytes one chunk expands to on device."""
        return self.staging.nbytes

    @property
    def wire_nbytes(self) -> int:
        """Encoded bytes one chunk actually moves across the link."""
        return sum(
            self.staging.n_shards * r * np.dtype(dt).itemsize
            for r, dt in zip(self.wire_row_elems, self.wire_dtypes)
        )

    @property
    def ratio(self) -> float:
        """logical/wire — >1 means the encoding is shrinking transfers."""
        w = self.wire_nbytes
        return self.logical_nbytes / w if w else 1.0

    @property
    def kinds(self) -> tuple:
        """Distinct non-raw encodings in use (empty = fell back to raw)."""
        return tuple(sorted(
            {e.kind for e in self.encodings if e.kind != "raw"}
        ))

    @property
    def is_lossless(self) -> bool:
        return all(e.kind in _LOSSLESS_KINDS for e in self.encodings)

    def encode(self, buffers: Sequence[np.ndarray]) -> tuple:
        """Encode one chunk's staged buffers into wire buffers (host
        side, once per chunk at compression setup — never per pass)."""
        st = self.staging
        wire = [
            np.zeros((st.n_shards, r), dt)
            for r, dt in zip(self.wire_row_elems, self.wire_dtypes)
        ]
        for slot, enc in zip(st.slots, self.encodings):
            seg = np.asarray(buffers[slot.buffer])[
                :, slot.offset : slot.offset + slot.size
            ]
            dst = wire[enc.wire_buffer][
                :, enc.wire_offset : enc.wire_offset + enc.wire_size
            ]
            if enc.kind == "raw":
                dst[...] = seg
            elif enc.kind == "downcast":
                dst[...] = seg.astype(dst.dtype)
            elif enc.kind == "delta":
                d = seg.astype(np.int64)
                d[:, 1:] -= seg[:, :-1].astype(np.int64)
                dst[...] = d.astype(dst.dtype)
            elif enc.kind == "bitmap":
                dst[...] = np.packbits(seg != 0, axis=1)
            elif enc.kind == "fp16":
                dst[...] = seg.astype(np.float16)
            else:  # int8
                m = np.max(np.abs(seg), axis=1, keepdims=True)
                sc = np.where(m > 0.0, m / 127.0, 1.0).astype(np.float32)
                wire[self.scale_buffer][
                    :,
                    self.scale_offset + enc.scale_index
                    : self.scale_offset + enc.scale_index + 1,
                ] = sc
                dst[...] = np.clip(
                    np.rint(seg / sc), -127, 127
                ).astype(np.int8)
        return tuple(wire)

    def unpack_device(self, wire):
        """The compiled on-device decode + unpack: slice, cast, cumsum
        and bit-shift arithmetic only, traced into the per-chunk program
        (the in-program dequant step).  Replaces
        :func:`unpack_device` for compressed items and obeys the same
        shard_map contract — all slicing is relative, the leading dim is
        read off the traced buffer, and per-shard scales arrive inside
        the (sharded) float32 wire buffer."""
        import jax.numpy as jnp
        from jax import lax

        st = self.staging
        scales = None
        if self.n_scales:
            scales = lax.slice_in_dim(
                wire[self.scale_buffer],
                self.scale_offset,
                self.scale_offset + self.n_scales,
                axis=1,
            )
        leaves = []
        for slot, enc in zip(st.slots, self.encodings):
            buf = wire[enc.wire_buffer]
            seg = lax.slice_in_dim(
                buf, enc.wire_offset, enc.wire_offset + enc.wire_size,
                axis=1,
            )
            odt = jax.dtypes.canonicalize_dtype(st.dtypes[slot.buffer])
            if enc.kind == "downcast":
                seg = seg.astype(odt)
            elif enc.kind == "delta":
                # Exact by modular arithmetic: the deltas were computed
                # from values that fit ``odt``, so their running integer
                # sum reconstructs every value bitwise even where an
                # intermediate wraps.
                seg = jnp.cumsum(seg.astype(odt), axis=1)
            elif enc.kind == "bitmap":
                shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
                bits = (seg[:, :, None] >> shifts) & jnp.uint8(1)
                seg = lax.slice_in_dim(
                    bits.reshape((bits.shape[0], -1)), 0, slot.size,
                    axis=1,
                ).astype(odt)
            elif enc.kind == "fp16":
                seg = seg.astype(odt)
            elif enc.kind == "int8":
                sc = lax.slice_in_dim(
                    scales, enc.scale_index, enc.scale_index + 1, axis=1
                )
                seg = seg.astype(odt) * sc
            if st.n_shards == 1:
                leaves.append(seg.reshape(slot.shape))
            else:
                leaves.append(
                    seg.reshape((buf.shape[0],) + slot.shard_shape)
                )
        return jax.tree_util.tree_unflatten(st.treedef, leaves)


def _narrowest(ladder, lo: int, hi: int, max_itemsize: int):
    """Narrowest ladder dtype (strictly below ``max_itemsize``) that
    holds every value in [lo, hi], or None."""
    for dt in ladder:
        if np.dtype(dt).itemsize >= max_itemsize:
            return None
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return dt
    return None


def _plan_int_slot(dt, segments: list):
    """delta/downcast choice for one integer slot: the narrowest wire
    dtype over BOTH the raw range and the per-row delta range (delta
    wins ties' complement — it needs a cumsum on device, so it must buy
    strictly more narrowing than a plain downcast)."""
    vmin = min(int(s.min()) for s in segments)
    vmax = max(int(s.max()) for s in segments)
    signed = np.dtype(dt).kind == "i"
    ladder = _SIGNED_LADDER if signed else _UNSIGNED_LADDER
    down = _narrowest(ladder, vmin, vmax, np.dtype(dt).itemsize)
    delta = None
    if signed:
        # Only each shard row's FIRST element rides the delta wire raw,
        # so the wire range is (first-column values) ∪ (pairwise deltas)
        # — not the full value range.
        dmin = min(int(s[:, 0].min()) for s in segments)
        dmax = max(int(s[:, 0].max()) for s in segments)
        for s in segments:
            if s.shape[1] < 2:
                continue
            d = s[:, 1:].astype(np.int64) - s[:, :-1].astype(np.int64)
            dmin = min(dmin, int(d.min()))
            dmax = max(dmax, int(d.max()))
        delta = _narrowest(
            _SIGNED_LADDER, dmin, dmax, np.dtype(dt).itemsize
        )
    if delta is not None and (
        down is None
        or np.dtype(delta).itemsize < np.dtype(down).itemsize
    ):
        return "delta", np.dtype(delta)
    if down is not None:
        return "downcast", np.dtype(down)
    return "raw", np.dtype(dt)


def _is_binary_f32(segments: list) -> bool:
    """Every element is BITWISE +0.0 or 1.0 — the strict precondition
    for the bitmap encoding to round-trip exactly (-0.0 would decode to
    +0.0, a bit flip)."""
    for s in segments:
        bits = np.ascontiguousarray(s).view(np.uint32)
        if not np.isin(bits, (0x00000000, 0x3F800000)).all():
            return False
    return True


def _is_binary_f64(segments: list) -> bool:
    """The f64 analogue of :func:`_is_binary_f32`: bitwise +0.0 or 1.0
    only (same -0.0 rejection — its bitmap decode would flip the sign
    bit)."""
    for s in segments:
        bits = np.ascontiguousarray(s).view(np.uint64)
        if not np.isin(
            bits, (0x0000000000000000, 0x3FF0000000000000)
        ).all():
            return False
    return True


def _f32_roundtrips_f64(segments: list) -> bool:
    """Every f64 value survives an f32 wire BITWISE (f64 -> f32 -> f64
    is the identity on the bit pattern), so a half-width wire is still
    lossless.  Indicator-heavy and low-precision feature blocks staged
    as f64 pass; anything needing the extra mantissa (or carrying NaN
    payloads f32 can't hold) falls back to raw."""
    for s in segments:
        rt = s.astype(np.float32).astype(np.float64)
        same = (
            np.ascontiguousarray(rt).view(np.uint64)
            == np.ascontiguousarray(s).view(np.uint64)
        )
        if not same.all():
            return False
    return True


def _bfloat16_dtype():
    """The registered bfloat16 numpy dtype, or None when ml_dtypes is
    absent (it ships with jax, so None is the exotic case)."""
    try:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    except Exception:  # pragma: no cover — ml_dtypes rides with jax
        return None


def _is_binary_bf16(segments: list) -> bool:
    """Bitwise +0.0 or 1.0 in bfloat16 (0x0000 / 0x3F80): the bitmap
    precondition for bf16-staged mask/indicator blocks."""
    for s in segments:
        bits = np.ascontiguousarray(s).view(np.uint16)
        if not np.isin(bits, (0x0000, 0x3F80)).all():
            return False
    return True


def plan_compression(
    staging: ChunkStaging, staged: Sequence, mode: str
) -> ChunkCodec | None:
    """Choose one wire encoding per staging slot, valid for EVERY chunk
    of the store (one scan over ``staged``), and lay the encoded slots
    out over wire buffers re-segregated by wire dtype.

    Returns None for mode "off".  A slot falls back to "raw" whenever
    its values rule the candidate encodings out (e.g. an int64 block
    whose values genuinely need 64 bits, or a float segment exceeding
    fp16 range in fp16 mode) — callers that REQUIRE a win should check
    :attr:`ChunkCodec.ratio` and fail loudly.
    """
    if mode == "off":
        return None
    if mode not in COMPRESSION_MODES:
        raise ValueError(
            f"compress must be one of {COMPRESSION_MODES}, got {mode!r}"
        )
    if not staged:
        raise ValueError("plan_compression needs a non-empty staged store")

    def segments(slot):
        return [
            np.asarray(bufs[slot.buffer])[
                :, slot.offset : slot.offset + slot.size
            ]
            for bufs in staged
        ]

    plans: list = []  # (kind, wire_dtype) per slot
    n_scales = 0
    for slot in staging.slots:
        dt = np.dtype(staging.dtypes[slot.buffer])
        if slot.size == 0:
            plans.append(("raw", dt))
            continue
        if dt.kind in "iu" and dt.itemsize >= 2:
            plans.append(_plan_int_slot(dt, segments(slot)))
            continue
        if dt == np.float64:
            # f64 staging is rare (x64-enabled hosts, double-precision
            # offsets) but pays double wire width for it — recover the
            # width wherever the VALUES don't need it, bitwise only.
            segs = segments(slot)
            if _is_binary_f64(segs):
                plans.append(("bitmap", np.dtype(np.uint8)))
                continue
            if _f32_roundtrips_f64(segs):
                plans.append(("downcast", np.dtype(np.float32)))
                continue
            plans.append(("raw", dt))
            continue
        bf16 = _bfloat16_dtype()
        if bf16 is not None and dt == bf16:
            segs = segments(slot)
            if _is_binary_bf16(segs):
                plans.append(("bitmap", np.dtype(np.uint8)))
                continue
            plans.append(("raw", dt))
            continue
        if dt == np.float32:
            segs = segments(slot)
            if _is_binary_f32(segs):
                plans.append(("bitmap", np.dtype(np.uint8)))
                continue
            if mode == "fp16":
                maxabs = max(float(np.max(np.abs(s))) for s in segs)
                if math.isfinite(maxabs) and maxabs <= 65504.0:
                    plans.append(("fp16", np.dtype(np.float16)))
                    continue
            elif mode == "int8":
                if all(np.isfinite(s).all() for s in segs):
                    plans.append(("int8", np.dtype(np.int8)))
                    n_scales += 1
                    continue
        plans.append(("raw", dt))

    # Wire layout: slots grouped by wire dtype, in first-appearance
    # order; the int8 scale sidecar claims float32 wire space FIRST so
    # its offset is independent of the (chunk-varying) data that
    # follows.  Bitmap wire length is the packed byte count.
    wire_dtypes: list = []
    wire_row_elems: list = []

    def wire_alloc(dt, elems: int) -> tuple:
        if dt not in wire_dtypes:
            wire_dtypes.append(dt)
            wire_row_elems.append(0)
        b = wire_dtypes.index(dt)
        off = wire_row_elems[b]
        wire_row_elems[b] += elems
        return b, off

    scale_buffer, scale_offset = -1, 0
    if n_scales:
        scale_buffer, scale_offset = wire_alloc(
            np.dtype(np.float32), n_scales
        )
    encodings: list = []
    scale_i = 0
    for slot, (kind, wdt) in zip(staging.slots, plans):
        elems = (
            (slot.size + 7) // 8 if kind == "bitmap" else slot.size
        )
        b, off = wire_alloc(wdt, elems)
        si = -1
        if kind == "int8":
            si = scale_i
            scale_i += 1
        encodings.append(_SlotEncoding(kind, b, off, elems, si))
    return ChunkCodec(
        staging=staging,
        mode=mode,
        encodings=tuple(encodings),
        wire_dtypes=tuple(wire_dtypes),
        wire_row_elems=tuple(wire_row_elems),
        n_scales=n_scales,
        scale_buffer=scale_buffer,
        scale_offset=scale_offset,
    )


# ---------------------------------------------------------------------------
# Wire dtype tags
# ---------------------------------------------------------------------------

#: Stable one-byte tags for the dtypes that may ride a binary wire
#: frame (serving/wire.py).  The table is append-only: tags are part of
#: the framed layout, so a tag must never be renumbered once a frame
#: version has shipped with it.  Segregating payload segments by dtype
#: tag is the same slot idiom :class:`ChunkCodec` uses for compressed
#: chunk buffers — a decoder maps each directory entry straight onto a
#: typed view of the payload, no per-element parsing.
WIRE_DTYPE_TAGS: tuple = (
    np.dtype(np.float32),   # 0
    np.dtype(np.float64),   # 1
    np.dtype(np.float16),   # 2
    np.dtype(np.int8),      # 3
    np.dtype(np.int16),     # 4
    np.dtype(np.int32),     # 5
    np.dtype(np.int64),     # 6
    np.dtype(np.uint8),     # 7
    np.dtype(np.uint16),    # 8
    np.dtype(np.uint32),    # 9
    np.dtype(np.uint64),    # 10
    np.dtype(np.bool_),     # 11
)

_WIRE_TAG_BY_DTYPE = {dt: i for i, dt in enumerate(WIRE_DTYPE_TAGS)}


def wire_dtype_tag(dtype) -> int:
    """The one-byte wire tag for ``dtype``; raises ``KeyError`` with the
    offending dtype named when it has no tag (complex, object, …)."""
    dt = np.dtype(dtype)
    tag = _WIRE_TAG_BY_DTYPE.get(dt)
    if tag is None:
        raise KeyError(
            f"dtype {dt} has no wire tag; supported: "
            f"{[str(d) for d in WIRE_DTYPE_TAGS]}"
        )
    return tag


def wire_dtype_from_tag(tag: int) -> np.dtype:
    """Inverse of :func:`wire_dtype_tag`; raises ``KeyError`` on an
    unknown tag so decoders refuse rather than misread."""
    if not 0 <= tag < len(WIRE_DTYPE_TAGS):
        raise KeyError(f"unknown wire dtype tag {tag}")
    return WIRE_DTYPE_TAGS[tag]
