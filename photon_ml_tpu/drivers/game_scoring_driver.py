"""GAME scoring driver.

The analogue of the reference's ``GameScoringDriver`` (SURVEY.md §2, §3.3):
load a saved GameModel, read GAME Avro data through the SAVED index maps
(unseen features drop, as the reference's scoring path does), score (fixed
effect matvec + per-entity random-effect gathers, summed with offsets), and
write ``ScoringResultAvro`` records.

The scoring math is the serving subsystem's (``serving/kernels.py``, via
``GameTransformer``): batch jobs here and the online request path
(``python -m photon_ml_tpu.serving``) share ONE implementation of the
fixed-effect matvec + random-effect gather + offset sum, so a model
validated offline scores identically when deployed behind the
micro-batched HTTP endpoint (docs/serving.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.game_reader import read_game_avro
from photon_ml_tpu.evaluation.evaluators import get_evaluator
from photon_ml_tpu.game.estimator import GameTransformer
from photon_ml_tpu.io import avro
from photon_ml_tpu.io.game_store import load_game_model

from photon_ml_tpu.utils.compile_cache import (
    add_compile_cache_arg,
    enable_from_args,
)
from photon_ml_tpu.utils.device_report import (
    CompileClock,
    describe_devices,
    runtime_block,
)
from photon_ml_tpu.utils.logging import PhotonLogger
from photon_ml_tpu.utils.timer import Timer


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game_scoring_driver", description="TPU-native GAME batch scoring"
    )
    p.add_argument("--data", required=True, help="GAME Avro file to score")
    p.add_argument("--model-dir", required=True, help="saved GameModel directory")
    p.add_argument("--output-dir", required=True)
    p.add_argument(
        "--mean", action="store_true",
        help="emit mean responses (inverse link) instead of raw margins",
    )
    p.add_argument("--evaluator", help="also compute a metric if labels present")
    p.add_argument(
        "--device-metrics",
        action="store_true",
        help="compute the metric ON DEVICE; with --stream-block-rows and "
        "a pointwise evaluator (rmse/logistic_loss/poisson_loss/"
        "squared_loss) the metric accumulates as two scalars per block — "
        "NO per-row columns are retained, so memory stays one block even "
        "with a metric (AUC still needs the full column: global sort)",
    )
    p.add_argument(
        "--stream-block-rows",
        type=int,
        default=0,
        help="out-of-core scoring: read, score, and write the data in "
        "bounded blocks of about this many rows — memory is one block, "
        "never the dataset (plus 12 B/row of score/label/weight columns "
        "kept ONLY when --evaluator needs a global metric; the reference "
        "scores arbitrary-size data via Spark partitions, SURVEY.md 3.3). "
        "0 = materialize the whole file",
    )
    p.add_argument(
        "--telemetry",
        choices=["on", "off"],
        default="on",
        help="unified telemetry (events.jsonl + trace.json + metrics.json "
        "in the output dir, summary in the log)",
    )
    add_compile_cache_arg(p)
    return p


def run(argv: Optional[Sequence[str]] = None) -> dict:
    args = build_arg_parser().parse_args(argv)
    os.makedirs(args.output_dir, exist_ok=True)
    from photon_ml_tpu import telemetry as telemetry_mod

    with PhotonLogger(args.output_dir) as logger:
        tel = telemetry_mod.Telemetry(
            output_dir=args.output_dir,
            logger=logger,
            enabled=args.telemetry != "off",
        )
        with tel, tel.span(
            "run", driver="game_scoring_driver"
        ), CompileClock() as clock:
            return _run_impl(args, logger, tel, clock)


def _run_impl(args, logger, tel, clock) -> dict:
    timer = Timer().start()
    cache_dir = enable_from_args(args, logger)
    from photon_ml_tpu.parallel.multihost import initialize_logged

    initialize_logged(logger)
    logger.info("device: %s", describe_devices())

    model, index_maps = load_game_model(os.path.join(args.model_dir, "models"))
    transformer = GameTransformer(model, logger=logger)
    out_path = os.path.join(args.output_dir, "scores.avro")

    def score_block(uids, scores, labels, ids):
        # ONE columnar block shape for both paths — the streamed/resident
        # parity tests assert bit-for-bit identical output files.  Sorted
        # keys: the upstream ids dict order is insertion order
        # (whole-file for the resident reader, block-local for the
        # streamed one), so a canonical order here is what actually
        # makes the two output files byte-identical.  The writer
        # serializes natively (native/score_encoder.cpp) when available.
        return (
            uids,
            np.asarray(scores, np.float32),
            np.asarray(labels, np.float32),
            {k: ids[k] for k in sorted(ids)},
        )

    if args.stream_block_rows > 0:
        # Out-of-core: decode → score → write per bounded block.  The
        # score/label/weight columns (12 B/row) accumulate across blocks
        # ONLY when a global metric needs them; without --evaluator the
        # footprint stays one block.
        from photon_ml_tpu.data.game_reader import iter_game_avro
        from photon_ml_tpu.game.model import RandomEffectModel

        stream_kind = None
        if args.evaluator and args.device_metrics:
            from photon_ml_tpu.evaluation.device import pointwise_kind_for

            stream_kind = pointwise_kind_for(get_evaluator(args.evaluator))
        # Pointwise device metrics accumulate as (num, den) scalars per
        # block — no O(n_rows) column retention for the metric at all.
        keep_columns = bool(args.evaluator) and stream_kind is None
        partial_num = [0.0]
        partial_den = [0.0]
        all_scores: list[np.ndarray] = []
        all_labels: list[np.ndarray] = []
        all_weights: list[np.ndarray] = []
        n_streamed = [0]
        # Every block must expose the model's entity-id columns even if
        # none of its rows carry them (a block of id-less rows would
        # otherwise KeyError inside the random-effect scorer).
        entity_keys = [
            sub.entity_key
            for sub in model.models.values()
            if isinstance(sub, RandomEffectModel)
        ]

        def block_records():
            for shards, ids, response, weight, offset, uids in iter_game_avro(
                args.data, index_maps, block_rows=args.stream_block_rows,
                logger=logger, id_keys=entity_keys,
            ):
                blk = (
                    transformer.transform_with_mean(shards, ids, offset)
                    if args.mean
                    else transformer.transform(shards, ids, offset)
                )
                n_streamed[0] += len(blk)
                if keep_columns:
                    all_scores.append(np.asarray(blk, np.float32))
                    all_labels.append(response)
                    all_weights.append(weight)
                elif stream_kind is not None and len(blk):
                    from photon_ml_tpu.evaluation.device import (
                        device_pointwise_partial,
                    )

                    num, den = device_pointwise_partial(
                        jnp.asarray(np.asarray(blk, np.float32)),
                        jnp.asarray(response),
                        jnp.asarray(weight),
                        kind=stream_kind,
                    )
                    partial_num[0] += float(num)
                    partial_den[0] += float(den)
                logger.info("scored block of %d rows", len(blk))
                yield score_block(uids, blk, response, ids)

        # The columnar writer consumes the generator block-by-block:
        # rows stream to disk as they are produced, never as one list.
        avro.write_scoring_container(out_path, block_records())
        n_rows = n_streamed[0]
        if keep_columns:
            scores = np.concatenate(all_scores) if all_scores else (
                np.zeros(0, np.float32)
            )
            response = np.concatenate(all_labels) if all_labels else (
                np.zeros(0, np.float32)
            )
            weight = np.concatenate(all_weights) if all_weights else (
                np.zeros(0, np.float32)
            )
        else:
            scores = response = weight = None  # never needed without a metric
    else:
        shards, ids, response, weight, offset, uids, _ = read_game_avro(
            args.data, index_maps=index_maps, logger=logger
        )
        scores = (
            transformer.transform_with_mean(shards, ids, offset)
            if args.mean
            else transformer.transform(shards, ids, offset)
        )
        avro.write_scoring_container(
            out_path, [score_block(uids, scores, response, ids)]
        )
        n_rows = len(scores)

    result = {
        "n_rows": int(n_rows),
        "wall_seconds": timer.stop(),
        # GameTransformer scores on the HOST (scipy matvec + packed-table
        # gathers); only --mean / --device-metrics touch the device.
        "runtime": runtime_block(clock, cache_dir, "host scipy CSR"),
    }
    tel.gauge("scored_rows").set(int(n_rows))
    tel.gauge("run_wall_seconds").set(result["wall_seconds"])
    if args.evaluator:
        ev = get_evaluator(args.evaluator)
        if scores is None and args.stream_block_rows > 0:
            # Streamed + pointwise device metric: the per-block scalar
            # accumulation already holds the whole answer.
            from photon_ml_tpu.evaluation.device import (
                finish_pointwise_partial, pointwise_kind_for,
            )

            result["metric"] = finish_pointwise_partial(
                partial_num[0], partial_den[0], pointwise_kind_for(ev)
            )
        elif args.device_metrics:
            from photon_ml_tpu.evaluation.device import device_evaluator_fn

            fn = device_evaluator_fn(ev)
            result["metric"] = (
                float(fn(
                    jnp.asarray(scores), jnp.asarray(response),
                    None if weight is None else jnp.asarray(weight),
                ))
                if fn is not None
                else ev.evaluate(scores, response, weight)
            )
        else:
            result["metric"] = ev.evaluate(scores, response, weight)
        result["evaluator"] = type(ev).__name__
        logger.info("%s = %.6f", type(ev).__name__, result["metric"])
    with open(os.path.join(args.output_dir, "scoring_result.json"), "w") as f:
        json.dump(result, f, indent=2)
    logger.info("scored %d rows in %.2fs", result["n_rows"], result["wall_seconds"])
    return result


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
