"""Window kind ``cd_fit_multi``: ``cd_fit`` for a GAME model with any number
of random effects, read from the configuration's ``random_effects`` list
(each entry: the coordinate's name, its ``role`` in the metrics' names, its
shard and entity key, its solver settings and optionally
``max_rows_per_entity``, upstream's active-row cap).

As ``cd_fit`` (README_cd_fit.md, README_cd_fit_multi.md): data from the
configuration's ``data_seed`` mirrored by ``--seed``; the shards and entity
ids handed to the program's own ``GameEstimator.build_coordinates`` (timed:
the span ``data_ready``); ONE whole fit untimed; then whole fits back to
back through ``GameEstimator.fit_coordinates``, each from zero coefficients,
the train AUC after every update on the device.  The window closes with the
first fit that ends after ``--seconds`` and never before the traffic's
``min_fits``.  The unit of work is one coordinate UPDATE.

A ``Recorder`` (``cd_fit``'s) stands between the descent and each
coordinate.  ``check`` decides ``correct`` from one fit of the window,
drawn from the seed, every update of it against the float64 reference in
``reference_game_multi.py`` (PERF.md section 2).
"""

from __future__ import annotations

import dataclasses
import time
import types

import numpy as np

from benchmarks import reference_game_multi
from benchmarks.datagen import game_ml20m_multi
from benchmarks.windows import cd_fit
from benchmarks.windows.cd_fit import (  # noqa: F401  (run.py calls these)
    Recorder, Update, attempted_failed, end_to_end, free)


def _require_flat_passive_rows():
    """The cell holds 5.5 M passive rows.  A program from before they were
    stored flat (``game.data.PassiveRows``) pads them to the heaviest
    movie's 57 k and does not fit the chip's memory or its host's: say so
    at once, before any data is made."""
    from photon_ml_tpu.game import data

    if not hasattr(data, "PassiveRows"):
        raise SystemExit(
            "benchmarks/windows/cd_fit_multi.py: this program stores a "
            "capped random effect's passive rows lane-aligned and padded "
            "to the heaviest entity (no game.data.PassiveRows); the cell's "
            "5.5 M passive rows would not fit. No result.")


def _coordinate_configs(cfg):
    """The fixed effect's and every random effect's configuration, in the
    updating order (fixed first, then the list's): ``cd_fit``'s pair for
    each entry of the list, with the entry's active-row cap."""
    configs = {}
    for spec in cfg["random_effects"]:
        configs.update(
            cd_fit._coordinate_configs({**cfg, "random_effect": spec}))
        if spec.get("max_rows_per_entity") is not None:
            configs[spec["name"]] = dataclasses.replace(
                configs[spec["name"]],
                max_rows_per_entity=int(spec["max_rows_per_entity"]))
    return configs


def _rss_gb() -> dict:
    """The process's resident set now and at its highest, from /proc."""
    out = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(("VmRSS:", "VmHWM:")):
                    key, kb = line.split()[:2]
                    out[key[2:-1].lower()] = int(kb) * 1024 / 1e9
    except (OSError, ValueError):
        pass
    return out


def effect_shapes(cfg, host) -> dict:
    """Per random effect, from the generator's own counts: its role, width,
    entities and the rows it trains on and only scores under its cap."""
    n, out = host["n_rows"], {}
    for spec in cfg["random_effects"]:
        counts = np.bincount(host[spec["entity"]])
        active = game_ml20m_multi.rows_active(
            counts, spec.get("max_rows_per_entity"))
        width = {"genres": host["n_genres"] + 1,
                 "user_summary": host["n_dense"] // 2 + 1}[spec["kind"]]
        out[spec["name"]] = {
            "role": spec["role"], "dim": width, "entities": len(counts),
            "rows_active": active, "rows_passive": n - active,
            "heaviest": int(counts.max())}
    return out


def setup(run):
    """Everything before the window.  Fills ``run.state`` and the set-up
    spans in ``run.spans``."""
    _require_flat_passive_rows()
    cd_fit._require_counts()
    import jax

    from photon_ml_tpu.game.estimator import GameEstimator

    cfg = run.cfg
    rss = run.info["host_rss_gb"] = {"start": _rss_gb()}
    with run.span("datagen"):
        host = game_ml20m_multi.generate(cfg, run.seed)
        shards, ids = game_ml20m_multi.shards(host)
    rss["datagen"] = _rss_gb()
    labels = host["labels"]
    estimator = GameEstimator(
        cfg["task"], _coordinate_configs(cfg),
        n_iterations=int(cfg["cd_iterations"]),
        device_metrics=bool(cfg["device_metrics"]))
    with run.span("data_ready"):
        coordinates = estimator.build_coordinates(shards, ids, labels)
        jax.block_until_ready([
            jax.tree.leaves(getattr(c.dataset, "data", None) or (
                c.dataset.blocks, c.dataset.passive_blocks))
            for c in coordinates])
    # the per_movie shard is made inside the call (datagen/
    # game_ml20m_multi.py::_Shards): its seconds are datagen's
    run.spans["data_ready"] -= shards.lazy_seconds
    run.spans["datagen"] += shards.lazy_seconds
    del shards
    rss["data_ready"] = _rss_gb()
    log: list = []
    shape = {k: host[k] for k in (
        "n_rows", "n_users", "n_movies", "n_genres", "n_dense",
        "fixed_nnz", "random_nnz", "item_nnz")}
    shape["genre_tags"] = host["random_nnz"] - host["n_rows"]
    shape["nnz"] = shape["fixed_nnz"]  # as the layout readers call it
    shape["effects"] = effect_shapes(cfg, host)
    fixed = next(c for c in coordinates if c.kind == "fixed")
    # The reference makes its own copy from the seed once the window has
    # closed; the program's data needs the host's memory now.
    del host
    run.state.update(
        shape=shape, labels=labels, ids=ids, estimator=estimator,
        coordinates=coordinates, log=log,
        recorders=[Recorder(c, log) for c in coordinates],
        updates_per_fit=int(cfg["cd_iterations"]) * len(coordinates),
        feature_bytes=sum(
            x.nbytes for x in jax.tree.leaves(fixed.dataset.data.features)),
    )
    layouts = {c.name: c.feature_layout for c in coordinates}
    run.info["layout"] = layouts
    expect = cfg.get("expect_layout")
    if not run.dry and expect and not layouts[
            cfg["fixed_effect"]["name"]].startswith(expect):
        raise RuntimeError(
            f"the cell names the {expect} path but the fixed effect holds "
            f"its features as {layouts[cfg['fixed_effect']['name']]}")
    run.info["random_effects"] = {}
    for c in coordinates:
        if c.kind != "random":
            continue
        ds, want = c.dataset, shape["effects"][c.name]
        run.info["random_effects"][c.name] = {
            "entities": ds.n_entities, "rows_active": ds.rows_active,
            "rows_passive": ds.rows_passive,
            "generator": {k: want[k] for k in (
                "entities", "rows_active", "rows_passive", "heaviest")},
            "block_bytes": sum(x.nbytes for x in jax.tree.leaves(ds.blocks)),
            "passive_bytes": sum(
                x.nbytes for x in jax.tree.leaves(ds.passive_blocks)),
            "blocks": [
                {"lanes": b.n_entities, "rows": b.rows_per_entity,
                 "dim": b.block_dim, "x_minor": b.x_minor, "rows_real": real,
                 "rows_passive": 0 if p is None else p.n_rows}
                for b, real, p in zip(ds.blocks, ds.block_rows_real,
                                      ds.passive_blocks)]}
        if (ds.rows_active, ds.rows_passive) != (
                want["rows_active"], want["rows_passive"]):
            raise RuntimeError(
                f"coordinate {c.name!r} trains on {ds.rows_active} rows and "
                f"only scores {ds.rows_passive}; by the generator's counts "
                f"and the cap it is {want['rows_active']} and "
                f"{want['rows_passive']}")
    with run.span("warm_pass"):
        cd_fit._one_fit(run)
        log.clear()
    rss["warm_pass"] = _rss_gb()


def window(run, seconds):
    """The timed window; returns its fits' ends and the fit kept for the
    check (a reservoir of one, drawn by the seed)."""
    import jax

    st = run.state
    log = st["log"]
    min_fits = int(run.traffic.get("min_fits", 1))
    draw = np.random.default_rng([run.seed % (1 << 63), 20])
    ends, finite, kept, kept_index, fixed_solves = [], [], None, None, []
    st["window_wall_start"] = time.time()
    start = time.perf_counter()
    while time.perf_counter() - start < seconds or len(ends) < min_fits:
        with jax.profiler.TraceAnnotation("grid"):
            history = cd_fit._one_fit(run)
        ends.append(time.perf_counter())
        finite.append(all(np.isfinite(h["score_norm"]) for h in history))
        if draw.random() * len(ends) < 1.0:
            kept, kept_index = list(log), len(ends) - 1
        fixed_solves += [rec["solve"] for rec in log if rec["kind"] == "fixed"]
        log.clear()
    with run.span("read_answers"):
        readers = _coefficient_readers(run)
        kept = [Update(rec, readers.get(rec["coordinate"])) for rec in kept]
    run.info["fit_ends_s"] = [round(e - start, 4) for e in ends]
    solves = [types.SimpleNamespace(iterations=int(i)) for i in
              jax.device_get([s.iterations for s in fixed_solves])]
    return {"start": start, "end": ends[-1], "fits": len(ends),
            "solves": solves,
            "updates": len(ends) * st["updates_per_fit"],
            "finite": finite, "checked_fit": kept_index, "answers": kept}


def _coefficient_readers(run) -> dict:
    """Per random effect: from its state (one ``(E, D)`` array a block, in
    the block's own columns) to ``(n_entities, width)`` in its shard's."""
    effects = run.state["shape"]["effects"]

    def reader(ds, shape):
        entity = [np.asarray(ids, dtype=np.int64) for ids in ds.entity_ids]
        col_maps = [np.asarray(b.col_map) for b in ds.blocks]

        def coef_of(state):
            coef = np.zeros((shape["entities"], shape["dim"]))
            for ent, cmap, block in zip(entity, col_maps, state):
                block = np.asarray(block, np.float64)
                lane, k = np.nonzero(cmap >= 0)
                coef[ent[lane], cmap[lane, k]] = block[lane, k]
            return coef

        return coef_of

    return {c.name: reader(c.dataset, effects[c.name])
            for c in run.state["coordinates"] if c.kind == "random"}


def make_reference(run, **kw):
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = game_ml20m_multi.generate(
            run.cfg, run.seed)
    cfg = run.cfg
    return reference_game_multi.MultiReference(
        host, cfg["fixed_effect"]["reg_weight"], cfg["random_effects"],
        loss=cfg["task"], **kw)


def compare(ref, answers, roles):
    """The numbers of one fit's updates against the reference, each the
    largest over the updates it applies to.  As ``cd_fit.compare``, with:

    <role>_grad_gap_max, _mean: per random effect (``user``, ``movie``), an
        entity's gradient over its ACTIVE rows at what was returned and the
        offsets handed in, beside the same entity's at zero (at least 1).
    offsets_gap:    the offsets a coordinate was handed against the SUM of
        all the other coordinates' scores by the reference.
    scores_gap:     over all rows, the passive ones too.
    inv_descent:    what the update minimises (the full objective over the
        rows the coordinate trains on: all of them but for a capped effect)
        before the update over the descent it made.
    """
    names = [up.coordinate for up in answers]
    beta = np.zeros(ref.cols["n_fixed"])
    coefs = {n: e.zeros() for n, e in ref.effects.items()}
    scores = {n: np.zeros(ref.n) for n in dict.fromkeys(names)}
    zero = np.zeros(ref.n)
    g_zero_norm = float(np.linalg.norm(
        ref.fixed_value_and_grad(beta, zero)[1]))
    per_update = []
    for up in answers:
        name = up.coordinate
        row = {"coordinate": name, "iterations": up.iterations}
        others = sum(s for n, s in scores.items() if n != name)
        rows = None if up.kind == "fixed" else ref.effects[name].active
        f_before = ref.objective(beta, coefs, others + scores[name], rows)
        if up.kind == "fixed":
            beta = up.coef
            mine = ref.fixed_scores(beta)
            f, g = ref.fixed_value_and_grad(beta, up.offsets, scores=mine)
            row["value_gap"] = abs(up.value - f) / abs(f)
            row["fixed_grad_gap"] = float(
                np.linalg.norm(up.grad - g)) / g_zero_norm
        else:
            coefs = {**coefs, name: up.coef}
            mine = ref.effect_scores(name, up.coef)
            at_zero = np.linalg.norm(ref.effect_grad(
                name, ref.effects[name].zeros(), up.offsets, scores=zero),
                axis=1)
            gap = np.linalg.norm(ref.effect_grad(
                name, up.coef, up.offsets, scores=mine), axis=1
            ) / np.maximum(at_zero, 1.0)
            row[f"{roles[name]}_grad_gap_max"] = float(gap.max())
            row[f"{roles[name]}_grad_gap_mean"] = float(gap.mean())
        scores[name] = mine
        row["offsets_gap"] = cd_fit._rel(up.offsets - others, others)
        row["scores_gap"] = cd_fit._rel(up.scores - mine, mine)
        f_after = ref.objective(beta, coefs, others + mine, rows)
        row["inv_descent"] = f_before / max(
            f_before - f_after, 1e-30 * f_before)
        row["objective"] = f_after
        per_update.append(row)
    keys = ["value_gap", "fixed_grad_gap"] + [
        f"{r}_grad_gap_{m}" for r in roles.values() for m in ("max", "mean")
    ] + ["offsets_gap", "scores_gap", "inv_descent"]
    out = {k: max(r[k] for r in per_update if k in r) for k in keys}
    return out, per_update, {"g_zero_norm": g_zero_norm}


def judge(ref, answers, limits, caps, roles):
    """``(correct, numbers, details)`` of one fit's updates: each number
    beside its limit."""
    got, per_update, scale = compare(ref, answers, roles)
    numbers = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    over = max(up.iterations - caps[up.coordinate] for up in answers)
    numbers["iters_over_cap"] = {"value": max(over, 0), "limit": 0}
    correct = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
                  for n in numbers.values())
    return correct, numbers, (per_update, scale)


def _judging(cfg):
    caps = {cfg["fixed_effect"]["name"]: int(cfg["max_iters"])}
    caps.update({s["name"]: int(s["max_iters"])
                 for s in cfg["random_effects"]})
    roles = {s["name"]: s["role"] for s in cfg["random_effects"]}
    return cfg["limits"], caps, roles


def check(run, win):
    """``(correct, numbers)`` of the fit drawn from the seed."""
    answers = win["answers"]
    how = _judging(run.cfg)
    with run.span("reference"):
        ref = make_reference(run)
        correct, numbers, (per_update, scale) = judge(ref, answers, *how)
    run.info["check"] = {"fit": win["checked_fit"], "per_update": per_update,
                         **scale}
    if run.control:
        with run.span("control"):
            run.info["control"] = {
                name: dict(zip(("correct", "numbers"), judge(
                    ref, wrong, *how)[:2]))
                for name, wrong in wrong_answers(run, ref, answers)}
    return correct, numbers


def wrong_answers(run, ref, answers):
    """The control and the planted faults, as answers in the place of the
    run's own (``--control 1``).  ``judge`` has to call each not correct."""
    def reported(reference, precision="f64"):
        return [
            up.with_answer(**dict(zip(("value", "grad"), (
                reference.fixed_value_and_grad(
                    up.coef, up.offsets, precision)))))
            if up.kind == "fixed" else up for up in answers]

    def offsets_from(scores_of):
        """Each update's offsets as the sum of what the others last
        handed back (``scores_of(update)``)."""
        latest, out = {}, []
        for up in answers:
            others = [s for n, s in latest.items() if n != up.coordinate]
            out.append(sum(others) if others else np.zeros(ref.n))
            latest[up.coordinate] = scores_of(up)
        return out

    # The control: the reference in the program's place, one precision
    # down: what the fixed effect reports, and every score handed on.
    bf16 = {id(up): (ref.fixed_scores(up.coef, "bf16") if up.kind == "fixed"
                     else ref.effect_scores(up.coordinate, up.coef, "bf16"))
            for up in answers}
    yield "bf16", [
        up.with_answer(scores=bf16[id(old)], offsets=off)
        for up, old, off in zip(reported(ref, "bf16"), answers,
                                offsets_from(lambda u: bf16[id(u)]))]
    # Fault: half of the batch left out, the rest counted double.
    half = np.zeros(ref.n)
    half[::2] = 2.0
    yield "half_batch", reported(make_reference(run, row_scale=half))
    # Fault: the rows that pad the data to whole tiles counted as rows.
    pad = -ref.n % 2048
    grad_pad = np.zeros(ref.cols["n_fixed"])
    grad_pad[ref.cols["intercept"]] = 0.5 * pad
    yield "padding_rows_counted", [
        up.with_answer(value=up.value + pad * np.log(2.0),
                       grad=up.grad + grad_pad)
        if up.kind == "fixed" else up for up in answers]
    roles = _judging(run.cfg)[2]
    for name, eff in ref.effects.items():
        # Fault: the heaviest entity's block dropped (never solved).
        worst = int(np.argmax(np.bincount(eff.entity)))

        def without(up, worst=worst):
            coef = up.coef.copy()
            coef[worst] = 0.0
            return up.with_answer(coef=coef)

        yield f"{roles[name]}_block_dropped", [
            without(up) if up.coordinate == name else up for up in answers]
        if eff.active is None:
            continue
        # Fault: the effect's passive rows left unscored: its scores are
        # zero there, and so is its part of the offsets it hands on.
        passive = ~eff.active
        unscored = offsets_from(
            lambda u: u.scores * eff.active if u.coordinate == name
            else u.scores)
        yield f"{roles[name]}_passive_rows_unscored", [
            up.with_answer(offsets=off, **(
                {"scores": up.scores * eff.active}
                if up.coordinate == name else {}))
            for up, off in zip(answers, unscored)]
        # Fault: the cap ignored: the heaviest capped entities trained on
        # all their rows (the float64 solve of that problem in the answer's
        # place; 32 of them keep the control's host time in seconds).
        counts = np.bincount(eff.entity[passive], minlength=eff.n_entities)
        capped = np.argsort(-counts, kind="stable")[
            :min(32, int((counts > 0).sum()))]

        def uncapped(up, name=name, capped=capped):
            coef = up.coef.copy()
            coef[capped] = ref.solve_entities(name, capped, up.offsets)
            return up.with_answer(
                coef=coef, scores=ref.effect_scores(name, coef))

        yield f"{roles[name]}_cap_ignored", [
            uncapped(up) if up.coordinate == name else up for up in answers]
    # Fault: offsets refreshed from one other coordinate only: each update
    # trains against what the update before it handed back, and no more.
    yield "offsets_from_one_coordinate", [answers[0]] + [
        up.with_answer(offsets=before.scores)
        for before, up in zip(answers, answers[1:])]
    # Fault: every update returns its state unchanged.
    def unchanged(up):
        zero = np.zeros_like(up.coef)
        if up.kind != "fixed":
            return up.with_answer(coef=zero, scores=np.zeros(ref.n))
        value, grad = ref.fixed_value_and_grad(zero, np.zeros(ref.n))
        return up.with_answer(coef=zero, scores=np.zeros(ref.n),
                              offsets=np.zeros(ref.n), value=value, grad=grad)

    yield "state_unchanged", [unchanged(up) for up in answers]
