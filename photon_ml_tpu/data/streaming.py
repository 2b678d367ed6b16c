"""Out-of-core GLM training data: a host-RAM chunk store streamed to HBM.

SURVEY.md §7 names "Host→device ingest bandwidth for 1B rows" as a hard
part of the port: the reference keeps the dataset as a persisted Spark RDD
across executor memory, re-scanned by every ``treeAggregate`` pass
(SURVEY.md §3.1).  The TPU analogue here: the dataset lives in HOST RAM as
a list of equal-shaped chunk pytrees, and every objective evaluation
streams them through the chip with double-buffered ``device_put`` —
HBM only ever holds ~2 chunks, so trainable dataset size is bounded by
host RAM (and, with the Avro block reader, by disk), not by HBM.

Design constraints that shape this module:

- **One compiled program must serve every chunk** — per-chunk shapes and
  pytree structure are uniformized at build time (row padding, a common
  nnz budget, :func:`~photon_ml_tpu.ops.sparse_pallas.uniformize_pallas_layouts`
  for the tiled layouts).  A retrace per chunk would dwarf the transfer
  cost.
- **Chunks move as coalesced staging buffers, and live there too.**  A
  chunk's pytree has dozens of small leaves (slot codes, spill triples,
  dense stripes...), and one ``device_put`` per leaf pays the
  transport's fixed per-transfer cost per LEAF instead of per CHUNK —
  the dominant term in the round-5 150× streamed-vs-resident gap.  At
  build time each finished chunk is therefore packed into a few
  dtype-segregated contiguous staging buffers (data/staging.py), shaped
  ``(n_shards, elems)`` so mesh placement shards a buffer exactly like
  the leaves it carries.  ``chunks[k]`` stays the familiar
  :class:`GlmData` pytree, but its numpy leaves are ZERO-COPY VIEWS
  into ``staged[k]`` — host consumers read leaves, the transfer layer
  moves buffers, and the store pays no second copy.  A transfer is
  1-3 large ``device_put`` calls + a compiled slice/reshape unpack
  fused into the per-chunk program (Snap ML's pinned-staging-buffer
  discipline, arXiv:1803.06333).
- **Chunks hold numpy leaves**, never device arrays: the whole point is
  that the resident set exceeds HBM.
- **Ingest is incremental**: :func:`streaming_from_blocks` re-cuts an
  arbitrary block stream (e.g. Avro ``iter_blocks``) at ``chunk_rows``
  boundaries as blocks arrive, building each chunk's device layout the
  moment it fills and dropping the raw rows — peak host memory is the
  finished chunk store plus ~one chunk of raw buffer, never a second full
  copy of the dataset.  Staging packs one chunk at a time, so the peak
  gains only ~one transient chunk copy.
- **Disk-backed stores spill the STAGING buffers** (1-3 ``.npy`` files
  per chunk, memmapped back; leaf views slice the memmap), so a
  disk-resident chunk still reaches the device as a few large paged
  reads, not dozens of small ones.
- **Padding discipline**: rows added to fill the last chunk carry weight 0
  (exactly like the mesh row-padding in parallel/distributed.py), so every
  objective/metric reduction is unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import numpy as np

from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.data.staging import (
    ChunkStaging,
    chunk_view,
    pack_chunk,
    plan_staging,
)
from photon_ml_tpu.ops.sparse import (
    DenseMatrix,
    SparseMatrix,
    canonicalize_coo,
    pad_coo_triples,
)


@dataclasses.dataclass
class StreamingGlmData:
    """A GLM dataset as a list of uniform host-resident chunks.

    ``chunks`` are :class:`GlmData` pytrees with numpy leaves, every chunk
    identical in structure and shape (the last one row-padded with weight
    0).  With ``n_shards > 1`` every array additionally carries a leading
    shard axis for data-parallel placement (the streamed analogue of
    parallel/distributed.DistributedGlmData).

    ``staged``/``staging``: the coalesced transfer representation — per
    chunk, a tuple of dtype-segregated contiguous staging buffers whose
    layout :class:`~photon_ml_tpu.data.staging.ChunkStaging` records.
    When present, ``chunks[k]``'s leaves are zero-copy views into
    ``staged[k]`` (no second host copy) and consumers transfer the
    buffers instead of the leaf pytree.  Builder-produced stores are
    always staged; :meth:`ensure_staged` retrofits hand-built RAM
    stores.
    """

    chunks: list  # list[GlmData], numpy leaves (views into staged[k])
    n_rows: int  # real (unpadded) row count over all chunks
    n_features: int
    chunk_rows: int  # rows per chunk (uniform, incl. padding)
    n_shards: int = 1
    staging: ChunkStaging | None = None
    staged: list | None = None  # per chunk: tuple of staging buffers

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    @property
    def weight_sum(self) -> float:
        return float(sum(np.sum(c.weights) for c in self.chunks))

    def nbytes(self) -> int:
        """Host bytes held by all chunk leaves (for HBM-vs-dataset checks)."""
        return int(sum(
            leaf.nbytes
            for c in self.chunks
            for leaf in jax.tree.leaves(c)
            if hasattr(leaf, "nbytes")
        ))

    @functools.cached_property
    def _has_nonzero_offsets(self) -> bool:
        return bool(any(np.any(c.offsets) for c in self.chunks))

    def has_nonzero_offsets(self) -> bool:
        """Whether any chunk carries data offsets.  Cached after the first
        call — the O(dataset) host scan must not repeat per consumer (a
        GAME config grid constructs one coordinate per grid point against
        the same cached stream)."""
        return self._has_nonzero_offsets

    def ensure_staged(self) -> bool:
        """Pack the chunks into coalesced staging buffers if they are not
        already (hand-built stores; builder output is pre-staged).

        Returns whether the store is staged afterwards.  Disk-backed
        (memmap-leaf) stores that were not staged at build time are left
        alone — packing them here would materialize the whole store in
        RAM, the exact bound the memmaps exist to avoid."""
        if self.staged is not None:
            return True
        if not self.chunks:
            return False
        if any(
            isinstance(leaf, np.memmap)
            for leaf in jax.tree_util.tree_leaves(self.chunks[0])
        ):
            return False
        staging = plan_staging(self.chunks[0], self.n_shards)
        staged, views = [], []
        for c in self.chunks:
            bufs = pack_chunk(staging, c)
            treedef = jax.tree_util.tree_structure(c)
            staged.append(bufs)
            # Replace the originals with views so the buffers hold the
            # only copy (packing is a re-residency, not a duplication).
            views.append(chunk_view(staging, bufs, treedef))
        self.staging = staging
        self.staged = staged
        self.chunks = views
        return True


def spill_tree(tree, dir_: str, tag: str):
    """Replace a pytree's numpy leaves with disk-backed memmaps (one
    ``.npy`` per leaf under ``dir_``).  Downstream code is agnostic:
    ``np.memmap`` is an ndarray, ``device_put`` pages it straight from
    disk, and ``np.asarray`` materializes transiently.  The spill step of
    the MEMORY_AND_DISK residency ladder (the reference persists its
    RDDs exactly so — SURVEY.md §2).  The chunk store itself no longer
    spills per-leaf — its final chunks go to disk as packed staging
    buffers (see the module docstring); this helper serves the
    random-effect datasets and the builder's transient pre-uniformization
    spill."""
    import os

    os.makedirs(dir_, exist_ok=True)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, np.ndarray) and leaf.size > 0:
            path = os.path.join(dir_, f"{tag}_{i}.npy")
            np.save(path, np.ascontiguousarray(leaf))
            out.append(np.load(path, mmap_mode="r"))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def spill_random_effect_dataset(dataset, dir_: str):
    """A host random-effect dataset with every block's leaves on disk —
    feeds the out-of-core coordinates when even the HOST copy exceeds
    RAM (the blocks page through the OS cache as pass groups slice
    them)."""
    import dataclasses as _dc

    return _dc.replace(
        dataset,
        blocks=[
            spill_tree(b, dir_, f"re_block{i}")
            for i, b in enumerate(dataset.blocks)
        ],
        passive_blocks=[
            None if b is None else spill_tree(b, dir_, f"re_passive{i}")
            for i, b in enumerate(dataset.passive_blocks)
        ],
    )


def make_streaming_glm_data(
    features,
    labels,
    weights=None,
    offsets=None,
    chunk_rows: int = 1 << 20,
    use_pallas: bool | str = "auto",
    depth_cap: int = 128,
    n_shards: int = 1,
    coo_budget: int | None = None,
    storage_dir: str | None = None,
) -> StreamingGlmData:
    """Cut already-materialized host data into uniform chunks.

    ``features``: numpy 2-D array or scipy sparse matrix.  A convenience
    wrapper over :func:`streaming_from_blocks` with the whole dataset as
    one block (the raw rows are the caller's array either way — no extra
    full copy is built; chunks are cut and their layouts built one at a
    time).
    """
    n = features.shape[0]
    weights = (
        np.ones(n, np.float32) if weights is None
        else np.asarray(weights, np.float32)
    )
    offsets = (
        np.zeros(n, np.float32) if offsets is None
        else np.asarray(offsets, np.float32)
    )
    return streaming_from_blocks(
        [(features, np.asarray(labels, np.float32), weights, offsets)],
        n_features=features.shape[1],
        chunk_rows=chunk_rows,
        use_pallas=use_pallas,
        depth_cap=depth_cap,
        n_shards=n_shards,
        coo_budget=coo_budget,
        storage_dir=storage_dir,
    )


def streaming_from_blocks(
    blocks: Iterable,
    n_features: int,
    chunk_rows: int = 1 << 20,
    use_pallas: bool | str = "auto",
    depth_cap: int = 128,
    n_shards: int = 1,
    coo_budget: int | None = None,
    storage_dir: str | None = None,
) -> StreamingGlmData:
    """Build the chunk store from an iterator of ``(X, y[, w[, o]])``
    blocks (e.g. Avro ``iter_blocks`` output), re-cut to ``chunk_rows``
    boundaries AS THEY ARRIVE: each chunk's device layout is built the
    moment it fills and its raw rows are dropped, so peak host memory is
    the finished chunk store plus about one chunk of raw buffer — the
    dataset is never materialized as one giant matrix.

    Blocks may be scipy sparse or numpy (the first block decides; later
    blocks are converted).  ``use_pallas`` chooses the tiled Pallas layout
    for sparse chunks ("auto": on TPU — matching make_glm_data's resident
    heuristic); layouts are built with ``col_permutation=False`` and
    uniformized at the end so one jitted program serves every chunk.
    ``n_shards > 1`` stacks each chunk into per-device row blocks on a
    leading shard axis — for the tiled layout, one per-shard layout each,
    uniformized across chunks × shards and stacked leaf-wise, so the
    streamed-DP shard_map program runs the Pallas kernels per shard.
    """
    import os
    import shutil

    import scipy.sparse as sp

    if chunk_rows <= 0:
        raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
    # storage_dir: DISK-backed store.  Each chunk's leaves spill to .npy
    # as the chunk finishes (ingest RAM stays ~one chunk + the raw
    # buffer) and again after cross-chunk uniformization (one padded
    # chunk in RAM at a time); the returned chunks hold memmap leaves
    # that page through the OS cache as training streams them — host
    # RAM stops bounding the trainable size, disk does (the reference's
    # MEMORY_AND_DISK RDD persistence).
    raw_dir = None
    if storage_dir is not None:
        os.makedirs(storage_dir, exist_ok=True)
        if os.listdir(storage_dir):
            # A reused directory would leave a prior (possibly larger)
            # build's chunk files alongside this one — a silent disk leak
            # in the directory whose purpose is bounding disk footprint.
            raise ValueError(
                f"storage_dir {storage_dir!r} is not empty; point each "
                "build at a fresh directory (or clear it first)"
            )
        raw_dir = os.path.join(storage_dir, "raw")
    if n_shards > 1 and chunk_rows % n_shards:
        chunk_rows = -(-chunk_rows // n_shards) * n_shards
    per_shard = chunk_rows // max(n_shards, 1)

    d = int(n_features)

    # Raw row buffer (≤ one chunk + one incoming block) and finished
    # chunks.  For the tiled-Pallas path the finished entry is a host
    # layout (uniformized at the end); for COO it is canonicalized
    # triples (padded to the global nnz budget at the end); dense chunks
    # are finished outright.
    buf_X: list = []
    buf_y: list = []
    buf_w: list = []
    buf_o: list = []
    buffered = 0
    finished: list = []
    vectors: list = []  # (labels, weights, offsets) per chunk, padded
    n_rows = 0
    mode = None  # "pallas" | "coo" | "dense", fixed by the first block

    def _decide_mode(first_sparse: bool) -> str:
        up = use_pallas
        if up == "auto":
            up = first_sparse and jax.default_backend() == "tpu"
        if up and not first_sparse:
            raise ValueError("use_pallas=True needs sparse features")
        return "pallas" if up else ("coo" if first_sparse else "dense")

    def _finish_chunk(X, y, w, o):
        """X has exactly ``chunk_rows`` rows (zero rows appended for the
        final partial chunk; their weights are 0)."""
        vectors.append((y, w, o))
        if mode == "pallas":
            from photon_ml_tpu.ops.sparse_pallas import build_pallas_host

            # One tiled layout per shard's row block, over (per_shard, d);
            # with n_shards == 1 that is the whole chunk.  All chunk×shard
            # layouts are uniformized together at the end, so one
            # shard_map program serves every chunk (streamed DP at the
            # kernel rate, not the COO rate).  Built and kept on the host:
            # chunk data never passes through a device here.
            shard_mats = []
            for s in range(max(n_shards, 1)):
                coo = X[s * per_shard:(s + 1) * per_shard].tocoo()
                shard_mats.append(build_pallas_host(
                    coo.row.astype(np.int64), coo.col.astype(np.int64),
                    coo.data.astype(np.float32), per_shard, d,
                    depth_cap=depth_cap, col_permutation=False,
                ))
            if raw_dir is not None:
                shard_mats = [
                    spill_tree(m, raw_dir, f"c{len(finished)}_s{s}")
                    for s, m in enumerate(shard_mats)
                ]
            finished.append(shard_mats)
        elif mode == "coo":
            shards = []
            for s in range(max(n_shards, 1)):
                block = X[s * per_shard:(s + 1) * per_shard]
                coo = block.tocoo()
                shards.append(canonicalize_coo(
                    coo.row, coo.col, coo.data.astype(np.float32),
                    per_shard, d,
                ))
            if raw_dir is not None:
                shards = [
                    spill_tree(t, raw_dir, f"c{len(finished)}_s{s}")
                    for s, t in enumerate(shards)
                ]
            finished.append(shards)
        else:
            dense = np.asarray(X, np.float32)
            feat = DenseMatrix(
                dense if n_shards == 1
                else dense.reshape(n_shards, per_shard, d)
            )
            if storage_dir is not None:
                # Dense needs no cross-chunk uniformization: spill the
                # FINAL leaves directly, no raw copy.
                feat = spill_tree(
                    feat, storage_dir, f"chunk{len(finished)}_X"
                )
            finished.append(feat)

    buf_off = 0  # rows of buf_X[0] already consumed by earlier cuts

    def _pop_rows(take: int):
        """Copy exactly ``take`` rows off the front of the buffer.  A
        cursor (``buf_off``) walks the straddling first entry instead of
        re-slicing its tail, so each cut touches one chunk's worth of rows
        — a single giant input block (the make_streaming_glm_data path) is
        never re-copied once per chunk."""
        nonlocal buffered, buf_off
        Xp, yp, wp, op = [], [], [], []
        got = 0
        while got < take:
            avail = buf_X[0].shape[0] - buf_off
            use = min(avail, take - got)
            lo, hi = buf_off, buf_off + use
            Xp.append(buf_X[0][lo:hi])
            yp.append(buf_y[0][lo:hi])
            wp.append(buf_w[0][lo:hi])
            op.append(buf_o[0][lo:hi])
            got += use
            buf_off += use
            if buf_off == buf_X[0].shape[0]:
                buf_X.pop(0)
                buf_y.pop(0)
                buf_w.pop(0)
                buf_o.pop(0)
                buf_off = 0
        buffered -= take
        X = (
            np.vstack(Xp) if mode == "dense"
            else sp.vstack(Xp).tocsr()
        )
        return X, np.concatenate(yp), np.concatenate(wp), np.concatenate(op)

    def _drain(final: bool) -> None:
        while buffered >= chunk_rows or (final and buffered > 0):
            take = min(buffered, chunk_rows)
            Xc, yc, wc, oc = _pop_rows(take)
            pad = chunk_rows - take
            if pad:
                if mode == "dense":
                    Xc = np.concatenate(
                        [Xc, np.zeros((pad, d), np.float32)]
                    )
                else:
                    Xc = sp.vstack(
                        [Xc, sp.csr_matrix((pad, d), dtype=np.float32)]
                    ).tocsr()
                yc = np.concatenate([yc, np.zeros(pad, np.float32)])
                wc = np.concatenate([wc, np.zeros(pad, np.float32)])
                oc = np.concatenate([oc, np.zeros(pad, np.float32)])
            _finish_chunk(Xc, yc, wc, oc)

    for block in blocks:
        X, y = block[0], block[1]
        m = X.shape[0]
        w = (
            np.asarray(block[2], np.float32)
            if len(block) > 2 and block[2] is not None
            else np.ones(m, np.float32)
        )
        o = (
            np.asarray(block[3], np.float32)
            if len(block) > 3 and block[3] is not None
            else np.zeros(m, np.float32)
        )
        if X.shape[1] != d:
            raise ValueError(
                f"block has {X.shape[1]} features, expected {d}"
            )
        if mode is None:
            mode = _decide_mode(sp.issparse(X))
        if mode == "dense":
            X = X.toarray() if sp.issparse(X) else np.asarray(X, np.float32)
        else:
            X = sp.csr_matrix(X) if not sp.issparse(X) else X.tocsr()
            X.sum_duplicates()
        buf_X.append(X)
        buf_y.append(np.asarray(y, np.float32))
        buf_w.append(w)
        buf_o.append(o)
        buffered += m
        n_rows += m
        _drain(final=False)
    if mode is None:
        raise ValueError("no blocks")
    _drain(final=True)

    staging_box: list = [None]  # ChunkStaging, planned on the first chunk
    staged: list = []

    def _finalize_chunk(gd: GlmData, k: int) -> GlmData:
        """Stage one finished uniform chunk: pack its leaves into the
        dtype-segregated coalesced buffers (RAM: the buffers become the
        only copy, leaves turn into views; disk: the BUFFERS are what
        spills — 1-3 memmapped ``.npy`` per chunk instead of one per
        leaf).  One chunk is transiently duplicated during the pack,
        matching the build's stated peak-memory discipline."""
        if staging_box[0] is None:
            staging_box[0] = plan_staging(gd, n_shards)
        plan = staging_box[0]
        old_files = [
            leaf.filename
            for leaf in jax.tree_util.tree_leaves(gd)
            if isinstance(leaf, np.memmap)
            and getattr(leaf, "filename", None)
        ]
        bufs = pack_chunk(plan, gd)
        if storage_dir is not None:
            spilled = []
            for b, buf in enumerate(bufs):
                path = os.path.join(storage_dir, f"chunk{k}_stage{b}.npy")
                np.save(path, buf)
                spilled.append(np.load(path, mmap_mode="r"))
            bufs = tuple(spilled)
            for path in old_files:
                # Finish-time per-leaf spills (the dense path) are
                # superseded by the packed buffers; removing them keeps
                # the directory's footprint at ~one staged store.
                try:
                    os.remove(path)
                except OSError:
                    pass
        treedef = jax.tree_util.tree_structure(gd)
        staged.append(bufs)
        # The view keeps this chunk's OWN metadata (host_coo cold-path
        # triples differ per chunk even though their shape class — and
        # so the staging plan — is uniform).
        return chunk_view(plan, bufs, treedef)

    # Finalize: uniform shapes across chunks, then stage.
    chunks = []
    if mode == "pallas":
        from photon_ml_tpu.ops.sparse_pallas import (
            uniformize_one,
            uniformize_targets,
        )

        n_sh = max(n_shards, 1)
        # Uniformize across chunks AND shards: every layout shares one
        # pytree structure/shape set, so the per-chunk program compiles
        # once and the stacked shard leaves carry one common leading axis
        # for the mesh sharding.  Targets come from a metadata-only pass;
        # each chunk then pads (and, with storage_dir, respills) ONE at a
        # time — on a disk-backed build, RAM never holds more than one
        # padded chunk.
        targets = uniformize_targets(
            [m for shard_mats in finished for m in shard_mats]
        )
        for k, (y, w, o) in enumerate(vectors):
            ms = [uniformize_one(m, targets) for m in finished[k]]
            if n_shards == 1:
                gd = GlmData(ms[0], y, w, o)
            else:
                feat = jax.tree.map(lambda *xs: np.stack(xs), *ms)
                gd = GlmData(
                    feat,
                    y.reshape(n_shards, per_shard),
                    w.reshape(n_shards, per_shard),
                    o.reshape(n_shards, per_shard),
                )
            chunks.append(_finalize_chunk(gd, k))
            finished[k] = None  # drop the pre-pad layouts as we go
    elif mode == "coo":
        budget = max(
            1,
            max(len(r) for shards in finished for (r, _, _) in shards),
        )
        if coo_budget is not None:
            # Pod runs: every process must pad its COO chunks to ONE
            # agreed budget or the global chunk shapes (and therefore
            # the compiled SPMD programs) diverge across processes.
            if coo_budget < budget:
                raise ValueError(
                    f"coo_budget={coo_budget} is below this store's "
                    f"largest per-shard chunk nnz ({budget})"
                )
            budget = coo_budget
        for k, (shards, (y, w, o)) in enumerate(zip(finished, vectors)):
            padded = [pad_coo_triples(*t, budget) for t in shards]
            if n_shards == 1:
                r, c, v = padded[0]
                feat = SparseMatrix(r, c, v, chunk_rows, d)
                gd = GlmData(feat, y, w, o)
            else:
                feat = SparseMatrix(
                    np.stack([p[0] for p in padded]),
                    np.stack([p[1] for p in padded]),
                    np.stack([p[2] for p in padded]),
                    per_shard, d,
                )
                gd = GlmData(
                    feat,
                    y.reshape(n_shards, per_shard),
                    w.reshape(n_shards, per_shard),
                    o.reshape(n_shards, per_shard),
                )
            chunks.append(_finalize_chunk(gd, k))
            finished[k] = None
    else:
        for k, (feat, (y, w, o)) in enumerate(zip(finished, vectors)):
            if n_shards == 1:
                gd = GlmData(feat, y, w, o)
            else:
                gd = GlmData(
                    feat,
                    y.reshape(n_shards, per_shard),
                    w.reshape(n_shards, per_shard),
                    o.reshape(n_shards, per_shard),
                )
            # Dense feature leaves spilled at finish time are packed
            # into the staging buffers here (and their per-leaf files
            # removed — the buffers supersede them).
            chunks.append(_finalize_chunk(gd, k))

    if raw_dir is not None:
        # The pre-uniformization spill is dead weight once the padded
        # chunks are on disk.
        shutil.rmtree(raw_dir, ignore_errors=True)

    return StreamingGlmData(
        chunks=chunks,
        n_rows=n_rows,
        n_features=d,
        chunk_rows=chunk_rows,
        n_shards=n_shards,
        staging=staging_box[0],
        staged=staged,
    )
