"""The most seconds the set-up's compiles of one program took (its
``trace_s`` + ``lower_s`` + ``backend_s`` over every record of that
``program`` name).

The ten largest go to the result line as ``compile_by_program`` (count, the
three phases, the cache outcomes, the seconds by layer span) and the whole
list in one row as ``compile_totals``.  Where a program compiled inside the
window (``compiles_in_window`` is then not 0) they are named, each with the
span it lay under, as ``compiled_in_window``."""

from benchmarks.metrics import _setup


def read(run):
    records = _setup.setup_compiles(run)
    if records is None:
        return None
    late = _setup.within(_setup.compile_records(), run.marks["window_start"],
                         run.marks["window_end"])
    if late:
        run.info["compiled_in_window"] = [
            {"program": r["program"], "span": _setup.span_label(r["span"]),
             "cache": r["cache"],
             "seconds": r["trace_s"] + r["lower_s"] + r["backend_s"]}
            for r in late]
    if not records:
        return None
    programs = _setup.by_program(records)
    run.info["compile_by_program"] = programs
    run.info["compile_totals"] = _setup.totals(records)
    return programs[0]["seconds"]
