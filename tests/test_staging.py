"""Coalesced pinned-staging ingest pipeline.

Three contracts pinned here (ISSUE 1 acceptance):

1. **Staging parity** — packing a chunk's leaves into dtype-segregated
   buffers and unpacking (host views AND the compiled device unpack) is
   bit-exact, for every layout (dense / COO / tiled-Pallas) and for
   sharded (leading shard axis) and unsharded chunks.
2. **Streamed ≡ resident through the coalesced path** — the streamed
   objective's value/grad still matches the resident objective now that
   chunks cross as staging buffers with an in-program unpack.
3. **Pipeline bounds & observability** — prefetch-depth edge cases
   (1, > n_chunks), the ≤depth liveness bound, error propagation, and
   the transfer-stat counters.
"""

import os
import threading

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

os.environ.setdefault("PHOTON_PALLAS_INTERPRET", "1")

from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.data.prefetch import TransferStats, run_prefetched
from photon_ml_tpu.data.staging import pack_chunk, plan_staging
from photon_ml_tpu.data.streaming import make_streaming_glm_data
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.optim.streaming import StreamingObjective
from photon_ml_tpu.ops import losses

LAYOUTS = ["dense", "coo", "pallas"]


def _problem(rng, n, d, layout, seed=11):
    if layout == "dense":
        X = rng.normal(size=(n, d)).astype(np.float32)
        logits = X @ (rng.normal(size=d) * 0.3)
    else:
        X = sp.random(
            n, d, density=0.15, random_state=seed, format="csr",
            dtype=np.float32,
        )
        X = sp.hstack(
            [sp.csr_matrix(np.ones((n, 1), np.float32)), X[:, 1:]]
        ).tocsr()
        logits = np.asarray(X @ (rng.normal(size=d) * 0.3)).ravel()
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    return X, y


def _stream(rng, layout, n_shards=1, n=640, d=24, chunk_rows=256):
    X, y = _problem(rng, n, d, layout)
    return X, y, make_streaming_glm_data(
        X, y, chunk_rows=chunk_rows, use_pallas=(layout == "pallas"),
        n_shards=n_shards, depth_cap=16,
    )


class TestStagingRoundtrip:
    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("n_shards", [1, 4])
    def test_pack_view_unpack_bit_exact(self, rng, layout, n_shards):
        _, _, stream = _stream(rng, layout, n_shards=n_shards)
        assert stream.staged is not None and stream.staging is not None
        staging = stream.staging
        # Dtype segregation keeps the per-chunk transfer count O(1).
        assert 1 <= staging.n_buffers <= 4
        assert len(stream.staged) == stream.n_chunks
        for bufs, chunk in zip(stream.staged, stream.chunks):
            leaves = jax.tree_util.tree_leaves(chunk)
            # Host views are ZERO-COPY into the staging buffers (no
            # second host copy of the dataset)...
            for leaf in leaves:
                assert any(
                    np.shares_memory(leaf, np.asarray(b)) for b in bufs
                ) or leaf.size == 0
            # ...and re-packing the views reproduces the buffers
            # bit-for-bit (pack/view are exact inverses).
            repacked = pack_chunk(staging, chunk)
            for a, b in zip(repacked, bufs):
                np.testing.assert_array_equal(a, np.asarray(b))
            # Total staged bytes account for every leaf byte.
            assert staging.nbytes == sum(
                np.asarray(b).nbytes for b in bufs
            )

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("n_shards", [1, 4])
    def test_device_unpack_matches_host(self, rng, layout, n_shards):
        """The compiled slice+reshape unpack restores every leaf exactly
        (no kernels involved — pure XLA, so this covers the Pallas
        layout's staging on CPU too)."""
        _, _, stream = _stream(rng, layout, n_shards=n_shards)
        staging = stream.staging
        unpack = jax.jit(lambda bufs: staging.unpack_device(bufs))
        for bufs, chunk in zip(stream.staged, stream.chunks):
            restored = unpack(jax.device_put(bufs))
            host = jax.tree_util.tree_leaves(chunk)
            dev = jax.tree_util.tree_leaves(restored)
            assert len(host) == len(dev)
            for h, d_ in zip(host, dev):
                assert h.shape == d_.shape and h.dtype == d_.dtype
                np.testing.assert_array_equal(np.asarray(d_), h)

    def test_plan_rejects_mismatched_chunk(self, rng):
        _, _, stream = _stream(rng, "coo")
        other = jax.tree_util.tree_map(
            lambda x: np.zeros((3,) + x.shape[1:], x.dtype),
            stream.chunks[0],
        )
        with pytest.raises(ValueError, match="staging plan"):
            pack_chunk(stream.staging, other)

    def test_ensure_staged_retrofits_hand_built_store(self, rng):
        """A directly-constructed store (no builder) stages on first
        consumer contact and keeps its values."""
        from photon_ml_tpu.data.streaming import StreamingGlmData

        X, y = _problem(rng, 300, 12, "dense")
        n = X.shape[0]
        chunks = [
            make_glm_data(X[i: i + 100], y[i: i + 100])
            for i in range(0, n, 100)
        ]
        host_chunks = [
            jax.tree_util.tree_map(np.asarray, c) for c in chunks
        ]
        store = StreamingGlmData(
            chunks=host_chunks, n_rows=n, n_features=12, chunk_rows=100
        )
        before = [
            [np.array(l) for l in jax.tree_util.tree_leaves(c)]
            for c in store.chunks
        ]
        assert store.ensure_staged()
        assert store.staged is not None
        for c, orig in zip(store.chunks, before):
            for leaf, o in zip(jax.tree_util.tree_leaves(c), orig):
                np.testing.assert_array_equal(np.asarray(leaf), o)


class TestCoalescedEquivalence:
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_value_grad_matches_resident(self, rng, layout):
        X, y, stream = _stream(rng, layout)
        sobj = StreamingObjective("logistic", stream)
        assert stream.staged is not None  # the coalesced path is live
        data = make_glm_data(X, y, use_pallas=False)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v_s, g_s = sobj.value_and_grad(w, l2_weight=0.5)
        v_r, g_r = obj.value_and_grad(w, data, l2_weight=0.5)
        assert float(jnp.abs(v_s - v_r)) < 1e-3 * max(1.0, abs(float(v_r)))
        assert float(jnp.abs(g_s - g_r).max()) < 1e-3

    @pytest.mark.parametrize("layout", ["dense", "coo"])
    def test_sharded_value_grad_matches_resident(self, rng, layout):
        """Streamed DP through the coalesced path: buffers placed
        sharded over the mesh, shard_map unpack, fused psum — same
        numbers as the resident single-device objective."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        X, y, stream = _stream(rng, layout, n_shards=n_dev, n=960)
        sobj = StreamingObjective("logistic", stream, mesh=mesh)
        data = make_glm_data(X, y, use_pallas=False)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v_s, g_s = sobj.value_and_grad(w, l2_weight=0.5)
        v_r, g_r = obj.value_and_grad(w, data, l2_weight=0.5)
        assert float(jnp.abs(v_s - v_r)) < 1e-3 * max(1.0, abs(float(v_r)))
        assert float(jnp.abs(g_s - g_r).max()) < 1e-3

    def test_sharded_pallas_matches_resident(self, rng):
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        X, y, stream = _stream(rng, "pallas", n_shards=n_dev, n=960)
        sobj = StreamingObjective("logistic", stream, mesh=mesh)
        data = make_glm_data(X, y, use_pallas=False)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v_s, g_s = sobj.value_and_grad(w, l2_weight=0.5)
        v_r, g_r = obj.value_and_grad(w, data, l2_weight=0.5)
        assert float(jnp.abs(v_s - v_r)) < 1e-3 * max(1.0, abs(float(v_r)))
        assert float(jnp.abs(g_s - g_r).max()) < 1e-3

    def test_scores_match_through_staging(self, rng):
        X, y, stream = _stream(rng, "coo")
        sobj = StreamingObjective("logistic", stream)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        np.testing.assert_allclose(
            sobj.scores(w),
            np.asarray(X @ np.asarray(w)).ravel(),
            atol=1e-4,
        )


class TestPrefetchDepth:
    @pytest.mark.parametrize("depth", [1, 3, 99])
    def test_any_depth_matches_double_buffer(self, rng, depth):
        """depth 1 (serial transfer/compute) and depth > n_chunks must
        produce bit-identical results to the default double buffer —
        chunks are consumed strictly in order regardless of depth."""
        X, y, stream = _stream(rng, "coo")
        assert depth != 2
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        ref = StreamingObjective("logistic", stream, prefetch_depth=2)
        v2, g2 = ref.value_and_grad(w, 0.5)
        sobj = StreamingObjective("logistic", stream, prefetch_depth=depth)
        v, g = sobj.value_and_grad(w, 0.5)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g2))
        assert sobj.transfer_stats.max_live <= depth

    def test_depth_exceeding_chunks(self, rng):
        X, y, stream = _stream(rng, "dense")
        sobj = StreamingObjective("logistic", stream, prefetch_depth=99)
        w = jnp.zeros(stream.n_features, jnp.float32)
        v, _ = sobj.value_and_grad(w)
        assert np.isfinite(float(v))
        assert sobj.transfer_stats.max_live <= stream.n_chunks

    def test_invalid_depth_rejected(self, rng):
        _, _, stream = _stream(rng, "dense")
        with pytest.raises(ValueError, match="prefetch_depth"):
            StreamingObjective("logistic", stream, prefetch_depth=0)


class TestTransferStats:
    def test_counters_after_one_pass(self, rng):
        X, y, stream = _stream(rng, "coo")
        sobj = StreamingObjective("logistic", stream)
        w = jnp.zeros(stream.n_features, jnp.float32)
        sobj.value_and_grad(w)
        st = sobj.transfer_stats
        assert st.passes == 1
        assert st.chunks == stream.n_chunks
        assert st.bytes == stream.n_chunks * stream.staging.nbytes
        assert st.h2d_seconds >= 0.0
        assert 1 <= st.max_live <= 2
        snap = st.snapshot()
        assert set(snap) >= {
            "chunks", "bytes", "h2d_seconds", "gbps", "chunk_seconds",
            "producer_stalls", "consumer_stalls", "max_live", "passes",
        }

    def test_accumulates_and_resets(self, rng):
        X, y, stream = _stream(rng, "dense")
        sobj = StreamingObjective("logistic", stream)
        w = jnp.zeros(stream.n_features, jnp.float32)
        sobj.value_and_grad(w)
        sobj.value_and_grad(w)
        st = sobj.transfer_stats
        assert st.passes == 2
        assert st.chunks == 2 * stream.n_chunks
        st.reset()
        assert st.passes == 0 and st.chunks == 0 and st.bytes == 0

    def test_scores_pass_counts_too(self, rng):
        X, y, stream = _stream(rng, "coo")
        sobj = StreamingObjective("logistic", stream)
        sobj.scores(jnp.zeros(stream.n_features, jnp.float32))
        assert sobj.transfer_stats.chunks == stream.n_chunks


class TestRunPrefetched:
    """The pipeline primitive itself, against plain numpy items."""

    def test_order_and_results(self):
        items = [np.full((4,), k, np.float32) for k in range(7)]
        seen = []
        run_prefetched(
            len(items),
            lambda k: items[k],
            lambda h: h * 2,
            lambda k, dev: seen.append((k, float(dev[0]))),
            depth=2,
        )
        assert seen == [(k, 2.0 * k) for k in range(7)]

    def test_liveness_bound_holds_at_put(self):
        counts = {"put": 0, "consumed": 0}
        violations = []
        depth = 3

        def put(h):
            counts["put"] += 1
            if counts["put"] - counts["consumed"] > depth:
                violations.append(dict(counts))
            return h

        run_prefetched(
            20,
            lambda k: np.zeros(1),
            put,
            lambda k, dev: counts.__setitem__(
                "consumed", counts["consumed"] + 1
            ),
            depth=depth,
        )
        assert not violations

    def test_producer_error_propagates(self):
        def get_item(k):
            if k == 2:
                raise RuntimeError("ingest exploded")
            return np.zeros(1)

        consumed = []
        with pytest.raises(RuntimeError, match="ingest exploded"):
            run_prefetched(
                5, get_item, lambda h: h,
                lambda k, dev: consumed.append(k), depth=2,
            )
        assert consumed == [0, 1]

    def test_consumer_error_stops_producer(self):
        stats = TransferStats()

        def consume(k, dev):
            if k == 1:
                raise ValueError("consumer bailed")

        with pytest.raises(ValueError, match="consumer bailed"):
            run_prefetched(
                50, lambda k: np.zeros(1), lambda h: h, consume,
                depth=2, stats=stats,
            )
        # The producer must wind down promptly (no leaked live thread
        # still transferring the remaining ~48 items).
        deadline = 50
        for _ in range(deadline):
            if not any(
                t.name == "h2d-prefetch" and t.is_alive()
                for t in threading.enumerate()
            ):
                break
            import time

            time.sleep(0.1)
        else:
            pytest.fail("producer thread still alive after consumer error")

    def test_empty_and_invalid(self):
        stats = TransferStats()
        assert run_prefetched(
            0, lambda k: None, lambda h: h, lambda k, d: None,
            depth=2, stats=stats,
        ) == 0
        assert stats.passes == 1
        with pytest.raises(ValueError, match="depth"):
            run_prefetched(
                1, lambda k: None, lambda h: h, lambda k, d: None, depth=0
            )


class TestStageAttribution:
    """The three-stage split (pack thread / transfer thread / consumer)
    must attribute wall time per stage, and the attribution must add up:
    dispatch ⊆ h2d, stage_seconds = pack + h2d + consume."""

    def test_stage_seconds_recorded_and_consistent(self):
        import time

        stats = TransferStats()

        def slow_get(k):
            time.sleep(0.002)
            return np.zeros(64, np.float32)

        def slow_put(h):
            time.sleep(0.002)
            return h

        def slow_consume(k, dev):
            time.sleep(0.002)

        run_prefetched(
            6, slow_get, slow_put, slow_consume, depth=2, stats=stats
        )
        assert stats.pack_seconds > 0.0
        assert stats.dispatch_seconds > 0.0
        assert stats.h2d_seconds >= stats.dispatch_seconds
        assert stats.consume_seconds > 0.0
        expect = (
            stats.pack_seconds + stats.h2d_seconds + stats.consume_seconds
        )
        assert abs(stats.stage_seconds - expect) < 1e-12
        snap = stats.snapshot()
        assert set(snap) >= {
            "pack_seconds", "dispatch_seconds", "consume_seconds",
            "stage_seconds",
        }

    def test_pack_runs_on_its_own_thread(self):
        """get_item must execute off BOTH the caller thread and the
        transfer thread — the split that lets packing overlap the link."""
        import threading

        names = set()

        def get_item(k):
            names.add(threading.current_thread().name)
            return np.zeros(8, np.float32)

        put_names = set()

        def put(h):
            put_names.add(threading.current_thread().name)
            return h

        run_prefetched(4, get_item, put, lambda k, d: None, depth=2)
        assert names == {"h2d-pack"}
        assert put_names == {"h2d-prefetch"}

    def test_pack_failure_propagates_in_order(self):
        """A pack-stage exception must surface at the failed item's
        position AFTER items 0..k-1 were consumed (the two-thread relay
        preserves stream order)."""
        consumed = []

        def get_item(k):
            if k == 3:
                raise RuntimeError("pack exploded")
            return np.zeros(4, np.float32)

        with pytest.raises(RuntimeError, match="pack exploded"):
            run_prefetched(
                8, get_item, lambda h: h,
                lambda k, d: consumed.append(k), depth=2,
            )
        assert consumed == [0, 1, 2]


# ---------------------------------------------------------------------------
# Compressed chunk formats: wire encodings + on-device decode
# ---------------------------------------------------------------------------

from photon_ml_tpu.data.staging import (  # noqa: E402
    COMPRESSION_MODES,
    plan_compression,
)


def _codec_roundtrip(stream, mode):
    """Encode every chunk and decode on device; returns (codec, list of
    (decoded leaves, reference leaves)) where the reference is the RAW
    staged path's device decode — the exact arrays the uncompressed
    stream would compute on."""
    staging = stream.staging
    codec = plan_compression(staging, stream.staged, mode)
    dec = jax.jit(codec.unpack_device)
    raw = jax.jit(staging.unpack_device)
    pairs = []
    for bufs in stream.staged:
        wire = codec.encode(bufs)
        got = jax.tree_util.tree_leaves(dec(jax.device_put(wire)))
        ref = jax.tree_util.tree_leaves(raw(jax.device_put(bufs)))
        pairs.append((got, ref))
    return codec, pairs


class TestChunkCodec:
    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("n_shards", [1, 4])
    def test_lossless_bitwise_and_smaller(self, rng, layout, n_shards):
        """'lossless' mode: every decoded device leaf is BITWISE the
        raw staged path's leaf, for every layout and sharding — the
        contract that lets compressed solves promise bit-identity —
        and the wire is actually smaller on these stores."""
        _, _, stream = _stream(rng, layout, n_shards=n_shards)
        codec, pairs = _codec_roundtrip(stream, "lossless")
        assert codec.is_lossless
        assert codec.ratio > 1.0
        assert codec.wire_nbytes < codec.logical_nbytes
        for got, ref in pairs:
            for g, r in zip(got, ref):
                assert g.dtype == r.dtype and g.shape == r.shape
                assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    @pytest.mark.parametrize("layout", ["dense", "coo"])
    def test_fp16_error_bounds(self, rng, layout):
        """fp16 mode: float32 value slots round-trip within half-
        precision error; integer and {0,1} slots stay bitwise exact
        (they keep their lossless encodings)."""
        _, _, stream = _stream(rng, layout)
        codec, pairs = _codec_roundtrip(stream, "fp16")
        assert not codec.is_lossless and "fp16" in codec.kinds
        for got, ref in pairs:
            for g, r in zip(got, ref):
                r_np = np.asarray(r)
                if r_np.dtype.kind != "f" or set(
                    np.unique(r_np)
                ) <= {0.0, 1.0}:
                    assert np.asarray(g).tobytes() == r_np.tobytes()
                else:
                    np.testing.assert_allclose(
                        np.asarray(g), r_np, rtol=1e-3, atol=1e-4
                    )

    @pytest.mark.parametrize("layout", ["dense", "coo"])
    def test_int8_error_bounds(self, rng, layout):
        """int8 mode: per-(shard-row, slot) symmetric quantization —
        absolute error ≤ maxabs/127 per slot (half a quantization step
        rounds to the nearest level, so one full step is a safe
        bound)."""
        _, _, stream = _stream(rng, layout)
        codec, pairs = _codec_roundtrip(stream, "int8")
        assert "int8" in codec.kinds
        for got, ref in pairs:
            for g, r in zip(got, ref):
                r_np = np.asarray(r)
                if r_np.dtype.kind != "f" or set(
                    np.unique(r_np)
                ) <= {0.0, 1.0}:
                    assert np.asarray(g).tobytes() == r_np.tobytes()
                else:
                    bound = np.abs(r_np).max() / 127 + 1e-7
                    assert np.abs(np.asarray(g) - r_np).max() <= bound

    def test_delta_beats_downcast_on_sorted_large_values(self):
        """A sorted int64 slot whose VALUES need 32 bits but whose
        per-row deltas (and first element — it rides the delta wire
        raw) fit 8 forces the delta encoding (cumsum decode), and the
        decode is bitwise exact."""
        base = np.arange(256, dtype=np.int64) * 100  # max 25500 > int8,
        # deltas all 100 -> delta wires int8, downcast needs int16
        chunk = {"idx": base.copy(), "v": np.ones(4, np.float32)}
        staging = plan_staging(chunk, 1)
        staged = [pack_chunk(staging, chunk)]
        codec = plan_compression(staging, staged, "lossless")
        kinds = {
            s.size: e.kind
            for s, e in zip(staging.slots, codec.encodings)
        }
        assert kinds[256] == "delta"
        got = jax.tree_util.tree_leaves(
            jax.jit(codec.unpack_device)(
                jax.device_put(codec.encode(staged[0]))
            )
        )
        ref = jax.tree_util.tree_leaves(
            jax.jit(staging.unpack_device)(jax.device_put(staged[0]))
        )
        for g, r in zip(got, ref):
            assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    def test_bitmap_rejects_negative_zero(self):
        """-0.0 is NOT bitwise +0.0: a slot containing it must refuse
        the bitmap encoding (whose decode emits +0.0) to keep the
        lossless guarantee strict."""
        ok = {"b": np.array([0.0, 1.0, 1.0, 0.0], np.float32)}
        st = plan_staging(ok, 1)
        codec = plan_compression(st, [pack_chunk(st, ok)], "lossless")
        assert codec.encodings[0].kind == "bitmap"
        bad = {"b": np.array([-0.0, 1.0, 1.0, 0.0], np.float32)}
        st2 = plan_staging(bad, 1)
        codec2 = plan_compression(st2, [pack_chunk(st2, bad)], "lossless")
        assert codec2.encodings[0].kind == "raw"

    def test_fp16_overflow_falls_back_to_raw(self):
        """A float slot exceeding fp16 range must stay raw rather than
        quantize to inf."""
        chunk = {"v": np.array([1e5, -2.0, 3.0, 4.0], np.float32)}
        st = plan_staging(chunk, 1)
        codec = plan_compression(st, [pack_chunk(st, chunk)], "fp16")
        assert codec.encodings[0].kind == "raw"

    def test_mode_off_and_unknown(self, rng):
        _, _, stream = _stream(rng, "coo")
        assert plan_compression(
            stream.staging, stream.staged, "off"
        ) is None
        with pytest.raises(ValueError, match="compress must be one of"):
            plan_compression(stream.staging, stream.staged, "zstd")
        assert set(COMPRESSION_MODES) == {"off", "lossless", "fp16", "int8"}


class TestChunkCodecWideFloats:
    """f64 and bf16 lossless planning: bitmaps for bitwise-{0,1} blocks,
    an f32 wire for f64 blocks whose every value round-trips bitwise,
    raw for everything else — the lossless guarantee stays strict."""

    def _roundtrip(self, chunk, mode="lossless"):
        from photon_ml_tpu.data.staging import plan_compression

        st = plan_staging(chunk, 1)
        staged = [pack_chunk(st, chunk)]
        codec = plan_compression(st, staged, mode)
        got = jax.tree_util.tree_leaves(
            jax.jit(codec.unpack_device)(
                jax.device_put(codec.encode(staged[0]))
            )
        )
        ref = jax.tree_util.tree_leaves(
            jax.jit(st.unpack_device)(jax.device_put(staged[0]))
        )
        return codec, got, ref

    def test_f64_binary_slot_bitmaps_bitwise(self):
        chunk = {
            "mask": np.array([0.0, 1.0, 1.0, 0.0, 1.0], np.float64),
            "v": np.linspace(-1, 1, 8, dtype=np.float32),
        }
        codec, got, ref = self._roundtrip(chunk)
        kinds = {
            s.size: e.kind
            for s, e in zip(codec.staging.slots, codec.encodings)
        }
        assert kinds[5] == "bitmap"
        assert codec.is_lossless
        assert codec.wire_nbytes < codec.logical_nbytes
        for g, r in zip(got, ref):
            assert g.dtype == r.dtype and g.shape == r.shape
            assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    def test_f64_bitmap_rejects_negative_zero(self):
        from photon_ml_tpu.data.staging import plan_compression

        # -0.0 must refuse the BITMAP (its decode emits +0.0, a bit
        # flip) — but it survives an f32 wire bitwise, so the planner
        # may still take the downcast; the sign bit rides along.
        bad = {"mask": np.array([-0.0, 1.0, 0.0], np.float64)}
        st = plan_staging(bad, 1)
        codec = plan_compression(st, [pack_chunk(st, bad)], "lossless")
        assert codec.encodings[0].kind == "downcast"
        wire = codec.encode(pack_chunk(st, bad))[0]
        assert np.signbit(wire.astype(np.float64)[0, 0])

    def test_f64_downcasts_to_f32_wire_when_bitwise_exact(self):
        # Every value exactly representable in f32: the codec must take
        # the half-width wire, and the WIRE itself must reconstruct the
        # f64 bit patterns (host check — device canonicalization may
        # narrow f64 anyway when x64 is off).
        vals = np.array([1.0, -0.5, 2.75, 1024.0, -3.125], np.float64)
        chunk = {"offs": vals.copy()}
        codec, got, ref = self._roundtrip(chunk)
        assert codec.encodings[0].kind == "downcast"
        assert codec.wire_dtypes[codec.encodings[0].wire_buffer] == (
            np.dtype(np.float32)
        )
        assert codec.is_lossless
        wire = codec.encode([pack_chunk(
            codec.staging, chunk
        )[0]])[codec.encodings[0].wire_buffer]
        back = wire.astype(np.float64)
        assert back.tobytes() == np.ascontiguousarray(
            vals.reshape(1, -1)
        ).tobytes()
        for g, r in zip(got, ref):
            assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    def test_f64_needing_full_mantissa_stays_raw(self):
        from photon_ml_tpu.data.staging import plan_compression

        # 0.1 and 1 + 2**-40 do NOT survive an f32 round-trip bitwise.
        chunk = {"offs": np.array([0.1, 1.0 + 2.0 ** -40], np.float64)}
        st = plan_staging(chunk, 1)
        codec = plan_compression(st, [pack_chunk(st, chunk)], "lossless")
        assert codec.encodings[0].kind == "raw"
        assert codec.is_lossless  # raw is still bitwise

    def test_bf16_binary_slot_bitmaps_bitwise(self):
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        chunk = {
            "mask": np.array([0.0, 1.0, 0.0, 1.0, 1.0, 0.0], bf16),
            "v": np.ones(4, np.float32),
        }
        codec, got, ref = self._roundtrip(chunk)
        kinds = {
            s.size: e.kind
            for s, e in zip(codec.staging.slots, codec.encodings)
        }
        assert kinds[6] == "bitmap"
        assert codec.is_lossless
        for g, r in zip(got, ref):
            assert g.dtype == r.dtype and g.shape == r.shape
            assert np.asarray(g).tobytes() == np.asarray(r).tobytes()

    def test_bf16_general_values_stay_raw(self):
        import ml_dtypes

        from photon_ml_tpu.data.staging import plan_compression

        bf16 = ml_dtypes.bfloat16
        chunk = {"v": np.array([0.25, 3.0, -1.5], bf16)}
        st = plan_staging(chunk, 1)
        codec = plan_compression(st, [pack_chunk(st, chunk)], "lossless")
        assert codec.encodings[0].kind == "raw"
        neg = {"v": np.array([-0.0, 1.0], bf16)}
        st2 = plan_staging(neg, 1)
        codec2 = plan_compression(st2, [pack_chunk(st2, neg)], "lossless")
        assert codec2.encodings[0].kind == "raw"
