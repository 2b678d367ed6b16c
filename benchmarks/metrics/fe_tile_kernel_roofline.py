"""The tile kernel's share of its roofline on the GAME cell: the least time
the chip could take for the entries the kernel is handed -- the fixed
effect's stored entries less the share the layout keeps in dense stripes
(the ``layout.build`` span's ``stripe_nnz_share``), each an indicator's
index of 2 bytes, plus one vector element per row and per column -- over
the mean device duration of the kernel's events.  Bound by bytes.  (The
accepted ``tile_kernel_roofline`` charges the kernel the whole product;
here 95% of the product's entries are in stripes, which other operations
read.)"""

from benchmarks import roofline, roofline_game
from benchmarks.metrics import _layer_spans


def read(run):
    t = run.trace
    made = _layer_spans.between(
        run, "data.make_glm_data", "process_start", "window_start")
    if t is None or not t.kernel_durations_s or len(made) != 1:
        return None
    built = _layer_spans.children(made[0], "layout.build")
    if len(built) != 1 or "stripe_nnz_share" not in built[0].get("attrs", {}):
        return None
    shape = run.state["shape"]
    tiled = shape["fixed_nnz"] * (1.0 - built[0]["attrs"]["stripe_nnz_share"])
    least = roofline_game.tiled_product_seconds(
        shape, tiled, roofline.peaks(run.device_kind))
    mean = sum(t.kernel_durations_s) / len(t.kernel_durations_s)
    return 100.0 * least / mean
