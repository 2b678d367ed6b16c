"""What the readers of the set-up's account share (PR 35): the program's
COMPILE RECORDS (``photon_ml_tpu.telemetry.compile_records()``: one per
backend compile, with the program's name, its tracing, lowering and backend
seconds, what the persistent cache did and the layer span it lay under) and
its layer spans (``_layer_spans``), both on ``time.perf_counter()``, the
clock of ``run.marks``.

Set-up is what lies between the marks ``first_device_op`` and
``window_start``; records are selected by those marks, never by position.
A program without compile records (a commit before PR 35) gives ``None``,
and every reader of them returns ``None``.  The arithmetic is in functions
of plain lists, so that it can be checked on recorded ones
(``tests/test_setup_readers.py``).
"""

from benchmarks.metrics import _layer_spans

#: The cache outcomes that are real compiles (``hit`` is the fourth).
MISSES = ("stored", "unstored", "off")


def compile_records():
    """Every compile record the program holds, or ``None`` on a program
    that files none."""
    try:
        from photon_ml_tpu.telemetry import compile_records as records
    except ImportError:
        return None
    return records()


def setup_compiles(run):
    """The records of the compiles that ENDED inside set-up; ``None``
    without the records."""
    records = compile_records()
    if records is None:
        return None
    return within(records, run.marks["first_device_op"],
                  run.marks["window_start"])


def within(records, lo, hi):
    return [r for r in records if lo <= r["ts"] and r["ts"] + r["dur"] <= hi]


def total(records, field, caches=None):
    """Sum of ``field`` over the records whose ``cache`` is one of
    ``caches`` (all of them where it is ``None``); ``None`` on no list."""
    if records is None:
        return None
    return sum(r[field] for r in records
               if caches is None or r["cache"] in caches)


def label(record):
    """A record's name for a result line: ``game.group[per_user]``,
    ``layout.orient[f]``, ``compile jit(f)``."""
    if record.get("type") == "compile":
        return "compile " + record["program"]
    attrs = record.get("attrs") or {}
    mark = attrs.get("coordinate") or attrs.get("side")
    return record["name"] + (f"[{mark}]" if mark else "")


def span_label(span):
    """The ``span`` of a compile record as one string, or ``none``."""
    if not span:
        return "none"
    mark = span.get("coordinate")
    return span["name"] + (f"[{mark}]" if mark else "")


def by_program(records, top=10):
    """The programs by the seconds their compiles took, largest first: per
    ``program`` name the count, the three phases' seconds (a hit's
    retrieval lies inside its ``backend_s``: counted once), how many
    records had each cache outcome, and the seconds under each layer
    span."""
    table = {}
    for r in records:
        row = table.setdefault(r["program"], {
            "program": r["program"], "seconds": 0.0, "count": 0,
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache": {}, "span": {}})
        seconds = r["trace_s"] + r["lower_s"] + r["backend_s"]
        row["seconds"] += seconds
        row["count"] += 1
        for phase in ("trace_s", "lower_s", "backend_s"):
            row[phase] += r[phase]
        row["cache"][r["cache"]] = row["cache"].get(r["cache"], 0) + 1
        where = span_label(r["span"])
        row["span"][where] = row["span"].get(where, 0.0) + seconds
    return sorted(table.values(), key=lambda row: -row["seconds"])[:top]


def totals(records):
    """The whole list in one row: how many records, and per cache outcome
    its count, backend and retrieval seconds (what the three sums leave
    out is a hit's ``backend_s`` less its ``retrieval_s``: the cache key's
    hashing)."""
    out = {"records": len(records),
           "trace_s": total(records, "trace_s"),
           "lower_s": total(records, "lower_s"), "cache": {}}
    for r in records:
        row = out["cache"].setdefault(
            r["cache"], {"n": 0, "backend_s": 0.0, "retrieval_s": 0.0})
        row["n"] += 1
        row["backend_s"] += r["backend_s"]
        row["retrieval_s"] += r["retrieval_s"]
    return out


def _only_child(run, name):
    """``(the run's one data.make_glm_data span, its one child called
    name)``, or ``(None, None)``: the fixed effect's in a GAME cell."""
    made = _layer_spans.between(
        run, "data.make_glm_data", "process_start", "window_start")
    kids = _layer_spans.children(made[0], name) if len(made) == 1 else []
    return (made[0], kids[0]) if len(kids) == 1 else (None, None)


def setup_place(run):
    """The set-up's one ``layout.place`` span, or ``None``."""
    return _only_child(run, "layout.place")[1]


def layout_phases(run):
    """Seconds of each child of the set-up's ``layout.build`` by its name
    (``layout.orient`` summed over its sides), with the parent's own under
    ``layout.build`` and its sibling ``layout.to_coo`` (the CSR's way to
    triples, before the build opens); ``None`` without the span.  The readers
    ``layout_col_perm_s`` and ``layout_orient_s`` put the whole table on
    the result line as ``layout_phases``."""
    made, built = _only_child(run, "layout.build")
    if built is None:
        return None
    phases = {"layout.build": built["dur"]}
    for r in _layer_spans.records():
        if r["parent"] == built["id"] or (
                r["parent"] == made["id"] and r["name"] == "layout.to_coo"):
            phases[r["name"]] = phases.get(r["name"], 0.0) + r["dur"]
    run.info["layout_phases"] = phases
    return phases


def group_phases(run):
    """``{coordinate: {phase: seconds}}`` over every ``game.group`` of the
    set-up, its six children by their names' last part and the parent's own
    seconds under ``game.group``; ``None`` where no ``game.group`` has
    children.  Also put on the result line as ``group_phases``."""
    groups = _layer_spans.between(
        run, "game.group", "first_device_op", "window_start")
    records = _layer_spans.records()
    out = {}
    for g in groups:
        kids = {r["name"].rsplit(".", 1)[1]: r["dur"] for r in records
                if r["parent"] == g["id"]
                and r["name"].startswith("game.group.")}
        if kids:
            out[(g.get("attrs") or {}).get("coordinate", "")] = {
                "game.group": g["dur"], **kids}
    if not out:
        return None
    run.info["group_phases"] = out
    return out


def group_phase_seconds(run, phase):
    phases = group_phases(run)
    if phases is None:
        return None
    return sum(p.get(phase, 0.0) for p in phases.values())


def uncovered(lo, hi, spans, compiles):
    """The intervals of ``[lo, hi]`` under no record, as ``(start, end,
    inside, before, after)``.

    What COVERS is a record with nothing finer inside it: a compile record,
    or a layer span that no other span names as its parent.  A span with
    children (``game.build``, ``data.make_glm_data``, ``cd.fit``) covers
    nothing by itself -- the seconds between its children are what this is
    for -- but it cuts the uncovered intervals at its ends and names the
    pieces inside it (``inside``: the innermost such span, or ``None``
    outside all of them: the harness's own code).  ``before`` and ``after``
    name the nearest record edge on either side (``start of`` / ``end of``
    a span or a compile, or one of the two marks)."""
    parents = {s["parent"] for s in spans if s.get("parent") is not None}
    covering = [s for s in spans if s["id"] not in parents] + list(compiles)
    holders = [s for s in spans if s["id"] in parents]
    cover = sorted((max(lo, r["ts"]), min(hi, r["ts"] + r["dur"]))
                   for r in covering)
    gaps, at = [], lo
    for start, end in cover:
        if end <= start:
            continue  # outside the interval
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if at < hi:
        gaps.append((at, hi))
    cuts = sorted({t for s in holders for t in (s["ts"], s["ts"] + s["dur"])})
    every = spans + list(compiles)
    # (time, duration, name): of the edges at one instant the longest
    # record's names the piece (the span, not its first or last child)
    edges = ([(lo, hi - lo, "first_device_op"), (hi, hi - lo, "window_start")]
             + [(r["ts"], r["dur"], "start of " + label(r)) for r in every]
             + [(r["ts"] + r["dur"], r["dur"], "end of " + label(r))
                for r in every])
    pieces = []
    for a, b in gaps:
        marks = [a] + [t for t in cuts if a < t < b] + [b]
        for start, end in zip(marks, marks[1:]):
            mid = (start + end) / 2
            around = [s for s in holders
                      if s["ts"] <= mid <= s["ts"] + s["dur"]]
            inside = min(around, key=lambda s: s["dur"]) if around else None
            before = max((e for e in edges if e[0] <= start), default=None)
            after = min((e for e in edges if e[0] >= end),
                        key=lambda e: (e[0], -e[1]), default=None)
            pieces.append((start, end, inside and label(inside),
                           before and before[2], after and after[2]))
    return pieces
