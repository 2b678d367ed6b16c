"""Block coordinate descent over GAME coordinates.

The analogue of the reference's ``CoordinateDescent`` ([CONFIRMED-BASELINE],
SURVEY.md §2, §3.2): iterate the (ordered) coordinate list; train each
coordinate against the *residual* scores of all the others (per-row offsets =
base offsets + sum of other coordinates' scores); refresh that coordinate's
scores; optionally evaluate validation metrics per iteration.

Device-side bookkeeping mirrors the reference's score RDD joins as pure
array updates: ``total`` holds base + Σ coordinate scores, and training
coordinate c uses ``total - scores[c]`` as its offsets — one subtract
instead of an (n-1)-way join.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.telemetry import layer_span
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.game.coordinates import Coordinate


def _optimizer_name(coord) -> Optional[str]:
    """Best-effort optimizer label for a coordinate's solver span (the
    config lives at different depths across coordinate flavors)."""
    cfg = getattr(coord, "config", None)
    if cfg is None:
        cfg = getattr(getattr(coord, "problem", None), "config", None)
    opt = getattr(getattr(cfg, "optimizer", None), "optimizer", None)
    return getattr(opt, "value", None)


def _state_to_device(st):
    """Recursively move a coordinate state (array, list of arrays, or
    nested — e.g. the factored (u_list, V)) onto the device."""
    if st is None:
        return None
    if isinstance(st, (list, tuple)):
        return [_state_to_device(s) for s in st]
    return jnp.asarray(st)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class _Deferred:
    """Placeholder for a device scalar awaiting the batched flush."""

    index: int
    kind: str  # "f" float, "i" int, "b" bool — per-dtype readback stacks


def _walk_scalars(obj, pred, fn):
    """Map ``fn`` over every leaf matching ``pred`` in nested dicts/lists
    (history entries are plain JSON-ish data plus metric scalars; anything
    else passes through untouched)."""
    if pred(obj):
        return fn(obj)
    if isinstance(obj, dict):
        return {k: _walk_scalars(v, pred, fn) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        vals = [_walk_scalars(v, pred, fn) for v in obj]
        return tuple(vals) if isinstance(obj, tuple) else vals
    return obj


@dataclasses.dataclass
class CoordinateDescentResult:
    states: dict  # coordinate name -> device state
    scores: dict  # coordinate name -> (N,) device scores
    history: list  # per (iteration, coordinate) log entries


class CoordinateDescent:
    """Reference: ``CoordinateDescent.optimize(coordinates, iterations)``.

    ``pipeline=True`` enables the hierarchical-execution overlap
    schedule: before blocking on coordinate c's solve, the NEXT
    coordinate's ``prestage`` hint fires, so its offset-independent host
    work (out-of-core slice packing, warm-start staging) runs during
    c's streamed solve/all-reduce.  The Gauss-Seidel data flow is
    untouched — each coordinate still trains against the residual of
    everything before it, in the same order — so the trajectory is
    bitwise identical to the serial schedule (pinned by
    tests/test_game_hierarchical.py); the overlap achieved lands on the
    ``game_coordinate_overlap_seconds`` counter.
    """

    def __init__(
        self, coordinates: Sequence[Coordinate], pipeline: bool = False
    ):
        names = [c.name for c in coordinates]
        assert len(set(names)) == len(names), f"duplicate coordinate names: {names}"
        self.coordinates = list(coordinates)
        self.pipeline = bool(pipeline)

    def run(
        self,
        base_offsets: Array,
        n_iterations: int = 1,
        eval_fn: Optional[Callable[[int, str, dict, dict], dict]] = None,
        logger=None,
        checkpointer=None,
        initial_states: Optional[dict] = None,
        locked: Sequence[str] = (),
    ) -> CoordinateDescentResult:
        """``eval_fn(iteration, coordinate_name, scores_by_coordinate,
        states_by_coordinate)`` is called after each coordinate update (the
        reference evaluates its validation suite there — states let it score
        a validation set against the freshly-updated coordinate); its dict
        return is recorded in history.

        ``initial_states`` (coordinate name → state) warm-starts from a
        prior model — the reference's "incremental training" (SURVEY.md
        §5.4): each coordinate's scores are seeded from its initial state so
        the first update already trains against the prior model's residuals.

        ``locked`` names coordinates that are PARTIAL-RETRAIN locked (the
        reference's partial retraining: retrain some coordinates against a
        prior model's others): a locked coordinate contributes its initial
        state's scores to every offset but is never retrained — so it must
        appear in ``initial_states`` (or the resumed checkpoint).

        ``checkpointer`` (io/checkpoint.CoordinateDescentCheckpointer)
        persists the loop state after every iteration; when it holds a saved
        state, the run RESUMES from the last completed iteration and
        reproduces the uninterrupted result bit-for-bit (the accumulated
        ``total``/scores are restored, not recomputed)."""
        base_offsets = jnp.asarray(base_offsets, jnp.float32)
        locked = set(locked)
        names = {c.name for c in self.coordinates}
        if not locked <= names:
            raise ValueError(
                f"locked coordinates {sorted(locked - names)} are not in "
                f"this descent's coordinate list {sorted(names)}"
            )
        if names and locked >= names:
            raise ValueError(
                "every coordinate is locked — nothing to train (a fully "
                "locked run would just re-emit the initial model)"
            )
        scores: dict[str, Array] = {
            c.name: jnp.zeros_like(base_offsets) for c in self.coordinates
        }
        states: dict[str, object] = {c.name: None for c in self.coordinates}
        total = base_offsets
        history: list[dict] = []
        start_it = 0

        saved = checkpointer.load() if checkpointer is not None else None
        if saved is not None:
            saved_locked = set(saved.get("locked", []))
            if saved_locked != locked:
                # A resume must train the same coordinates the
                # checkpointed run did — otherwise the finalized model's
                # coordinates were never trained against each other.
                raise ValueError(
                    "checkpoint was written with locked coordinates "
                    f"{sorted(saved_locked)} but this run locks "
                    f"{sorted(locked)}; clear the checkpoint or match "
                    "the locked set"
                )
            # A checkpoint supersedes initial states entirely (it already
            # includes any warm start the original run began from), so don't
            # waste a full scoring pass on states about to be overwritten.
            start_it = saved["iteration"] + 1
            total = jnp.asarray(saved["total"])
            for coord in self.coordinates:
                scores[coord.name] = jnp.asarray(saved["scores"][coord.name])
                states[coord.name] = _state_to_device(
                    saved["states"][coord.name]
                )
            history = list(saved["history"])
            if logger is not None:
                logger.info(
                    "resuming coordinate descent from iteration %d", start_it
                )
        elif initial_states:
            for coord in self.coordinates:
                st = initial_states.get(coord.name)
                if st is None:
                    continue
                st = _state_to_device(st)
                states[coord.name] = st
                s = coord.score(st)
                scores[coord.name] = s
                total = total + s

        # score_norm — and any DEVICE scalar an eval_fn left in its entry
        # (the estimator's device-metrics path returns them unmaterialized
        # for exactly this reason) — stays on device as long as possible:
        # a host readback is a device sync, and one per update serializes
        # the host against every coordinate solve.  Entries and their
        # scalars accumulate in
        # ``pending`` and are flushed in ONE batched readback — per
        # iteration when a logger/checkpointer needs values then (logs
        # must carry them; checkpoints persist history), otherwise once
        # at the END of the run, so the whole multi-iteration loop
        # pipelines on the device with a single host sync.
        pending: list[dict] = []
        # (its ``coordinate.train`` layer span, ``train_counts()``) of every
        # update since the last flush.
        counted: list[tuple] = []

        def read_counts():
            """One read of every waiting count; each joins its span."""
            if not counted:
                return
            leaves, tree = jax.tree.flatten([c for _span, c in counted])
            waiting = [i for i, v in enumerate(leaves)
                       if isinstance(v, jax.Array)]
            if waiting:
                values = np.asarray(jnp.stack([
                    jnp.asarray(leaves[i], jnp.float32) for i in waiting
                ])).tolist()
                for i, v in zip(waiting, values):
                    whole = jnp.issubdtype(leaves[i].dtype, jnp.integer)
                    leaves[i] = int(v) if whole else v
            for (span, _c), counts in zip(
                counted, jax.tree.unflatten(tree, leaves)
            ):
                span.amend(**counts)
                frozen = sum(
                    b["frozen_early"] for b in counts.get("buckets", ()))
                if frozen:
                    tel.counter("game_re_frozen_early_total").inc(frozen)
            counted.clear()

        def flush():
            """The blocking read, as one ``cd.flush`` layer span under
            ``cd.fit`` (``cd.iteration`` with a logger or a checkpointer):
            the host waits here for every program it has dispatched since
            the last flush, so in a resident fit this is where the
            device's seconds show on the host's clock."""
            with layer_span("cd.flush", updates=len(pending)):
                read_back()

        def read_back():
            read_counts()
            if not pending:
                return
            # Floating scalars stack at f64 under x64 so fp64 device
            # metrics (device_auc computes in f64 there) keep full
            # precision — f32→f64 casts are exact.  Int/bool scalars (a
            # user eval_fn recording counts/flags) would corrupt through
            # a float stack; they materialize via HOST-side numpy
            # stacking instead: with x64 off, a device jnp.stack would
            # funnel them through int32 and silently wrap counts above
            # 2^31, while numpy preserves each scalar's own dtype
            # (uint32 counts to 4e9 included).  That costs one readback
            # per int/bool scalar — paid only when one exists; the big
            # float stack keeps the single batched readback.
            x64 = jax.config.jax_enable_x64
            fdt = jnp.float64 if x64 else jnp.float32
            stacks = {"f": [], "i": [], "b": []}

            def grab(a):
                kind = (
                    "f" if jnp.issubdtype(a.dtype, jnp.floating)
                    else "b" if a.dtype == jnp.bool_
                    else "i"
                )
                stack = stacks[kind]
                stack.append(a)
                return _Deferred(len(stack) - 1, kind)

            staged = [
                _walk_scalars(
                    entry,
                    lambda o: isinstance(o, jax.Array) and o.ndim == 0,
                    grab,
                )
                for entry in pending
            ]
            vals = {
                k: (
                    np.asarray(
                        jnp.stack([jnp.asarray(v, fdt) for v in stack])
                    )
                    if k == "f"
                    else np.stack([np.asarray(v) for v in stack])
                )
                for k, stack in stacks.items() if stack
            }
            cast = {"f": float, "i": int, "b": bool}
            for entry, filled in zip(pending, staged):
                done = _walk_scalars(
                    filled,
                    lambda o: isinstance(o, _Deferred),
                    lambda m: cast[m.kind](vals[m.kind][m.index]),
                )
                entry.clear()
                entry.update(done)
                history.append(entry)
                if logger is not None:
                    logger.info(
                        "CD iter %d coordinate %s: %s", entry["iteration"],
                        entry["coordinate"],
                        {k: v for k, v in entry.items()
                         if k not in ("iteration", "coordinate")},
                    )
            pending.clear()

        for name in locked:
            if states[name] is None:
                raise ValueError(
                    f"locked coordinate {name!r} has no state to hold: "
                    "supply it via initial_states (a prior model) or a "
                    "resumed checkpoint"
                )

        tel = telemetry_mod.current()
        flush_per_iteration = logger is not None or checkpointer is not None
        trainable = [
            c for c in self.coordinates if c.name not in locked
        ]
        # Layer spans (docs/telemetry.md): cd.fit > cd.iteration >
        # coordinate.train / coordinate.score; with a hub they are its
        # spans too, around its own ``coordinate`` span (train + score).
        # They time the HOST: real wall for streamed/out-of-core
        # coordinates (their train blocks per pass), dispatch wall for
        # resident ones — the batched-flush design forbids a per-update
        # device sync, so the true per-iteration wall rides the
        # cd_iteration_seconds histogram measured across the flush below,
        # and what an update counted on the device joins its
        # ``coordinate.train`` span when the flush reads it.
        with layer_span(
            "cd.fit", iterations=n_iterations,
            coordinates=[c.name for c in trainable],
        ):
            for it in range(start_it, n_iterations):
                it_t0 = time.perf_counter()
                with layer_span("cd.iteration", iteration=it):
                    for ci, coord in enumerate(trainable):
                        offsets = total - scores[coord.name]
                        if self.pipeline and ci + 1 < len(trainable):
                            # Overlap hint: the next coordinate's
                            # offset-independent host packing runs while
                            # this one's solve owns the device/foreground.
                            # Its warm state is untouched by this update
                            # (only states[coord.name] changes below), so
                            # the staged payloads stay valid.
                            nxt = trainable[ci + 1]
                            nxt.prestage(states[nxt.name])
                        upd_t0 = time.perf_counter()
                        with tel.span(
                            "coordinate", coordinate=coord.name, iteration=it
                        ):
                            with layer_span(
                                "coordinate.train", coordinate=coord.name,
                                kind=coord.kind, iteration=it,
                                optimizer=_optimizer_name(coord),
                            ) as train_span:
                                state = coord.train(
                                    offsets, warm_state=states[coord.name]
                                )
                            counts = coord.train_counts()
                            if counts:
                                counted.append((train_span, counts))
                            with layer_span(
                                "coordinate.score", coordinate=coord.name,
                                iteration=it,
                                rows_passive=getattr(coord, "rows_passive", 0),
                            ):
                                new_score = coord.score(state)
                        states[coord.name] = state
                        total = offsets + new_score
                        scores[coord.name] = new_score

                        entry = {"iteration": it, "coordinate": coord.name}
                        if eval_fn is not None:
                            entry.update(eval_fn(it, coord.name, scores, states))
                        # The norm is just another deferred floating scalar —
                        # the flush walk materializes it with the metrics.
                        entry["score_norm"] = jnp.linalg.norm(new_score)
                        entry["wall_seconds"] = time.perf_counter() - upd_t0
                        pending.append(entry)
                    if flush_per_iteration:
                        flush()
                    if checkpointer is not None:
                        checkpointer.save(
                            it, total, scores, states, history,
                            locked=sorted(locked),
                        )
                    # The CD outer-iteration boundary (the distributed-CD
                    # resume point): iteration ``it`` is complete AND
                    # checkpointed; a kill here must resume at it+1
                    # bit-identically (docs/robustness.md).
                    chaos_mod.maybe_fail("cd.iteration", iteration=it)
                if flush_per_iteration and tel.enabled:
                    # The flush materialized device scalars (a real sync), so
                    # this iteration wall is achieved wall-clock, not
                    # dispatch rate.
                    tel.histogram("cd_iteration_seconds").observe(
                        time.perf_counter() - it_t0
                    )
            flush()
        return CoordinateDescentResult(states=states, scores=scores, history=history)
