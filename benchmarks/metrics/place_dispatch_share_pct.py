"""Share of the set-up's ``layout.place`` that the host spent INSIDE the
leaves' ``jnp.asarray`` calls (the ``dispatch_s`` of its ``leaves``: a cast,
a copy of a leaf that is not contiguous, staging), the rest being the one
wait for the link (``wait_s``).  The span's leaves go to the result line as
``place_leaves``, with each ``game.place``'s totals and its five leaves of
the longest dispatch."""

from benchmarks.metrics import _layer_spans, _setup


def _row(span, top=None):
    attrs = span["attrs"]
    leaves = attrs["leaves"]
    if top is not None:
        leaves = sorted(leaves, key=lambda x: -x["dispatch_s"])[:top]
    return {"dur_s": span["dur"], "wait_s": attrs.get("wait_s"),
            "bytes": attrs.get("bytes"), "n_leaves": len(attrs["leaves"]),
            "dispatch_s": sum(x["dispatch_s"] for x in attrs["leaves"]),
            "leaves": leaves}


def read(run):
    place = _setup.setup_place(run)
    if place is None or not place["dur"] or "leaves" not in place["attrs"]:
        return None
    rows = {"layout.place": _row(place)}
    for span in _layer_spans.between(
            run, "game.place", "first_device_op", "window_start"):
        if "leaves" in span.get("attrs", {}):
            rows[_setup.label(span)] = _row(span, top=5)
    run.info["place_leaves"] = rows
    return 100.0 * rows["layout.place"]["dispatch_s"] / place["dur"]
