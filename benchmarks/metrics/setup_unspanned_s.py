"""Seconds of set-up that lie under no record of the program's own:
``window_start`` less ``first_device_op``, less what the program's compile
records and its layer spans cover in between (``_setup.uncovered``: a span
with children covers only through them), less the harness's own
``run.spans["datagen"]``.

The five longest uncovered intervals go to the result line as
``setup_gaps``: seconds after ``first_device_op``, the span each lies
inside (``None``: the harness's own code, its data generation among it) and
the record edge before and after."""

from benchmarks.metrics import _layer_spans, _setup


def read(run):
    compiles = _setup.compile_records()
    if compiles is None:
        return None
    lo, hi = run.marks["first_device_op"], run.marks["window_start"]
    pieces = _setup.uncovered(lo, hi, _layer_spans.records(), compiles)
    longest = sorted(pieces, key=lambda p: p[0] - p[1])[:5]
    run.info["setup_gaps"] = [
        {"at_s": start - lo, "seconds": end - start, "inside": inside,
         "before": before, "after": after}
        for start, end, inside, before, after in longest]
    bare = sum(end - start for start, end, *_ in pieces)
    return max(0.0, bare - run.spans.get("datagen", 0.0))
