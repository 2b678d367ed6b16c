"""The whole window's share of the chip's peak: the least time the chip
could take for the iterations the window made, over the window's wall time.

Needed work per iteration, from SolveResult.iterations and the shapes alone
(never from the layout or the launch count): the configuration's
``products_per_iteration`` sparse products (L-BFGS: one value+gradient, a
forward and a backward product).  Further line-search trials are the
solver's choice, not needed work; ``passes_per_solve`` shows them.  Each
product is the larger of its operations over the peak FLOP/s and its bytes
over the peak bytes/s -- bytes, at 2 operations for every 6 bytes.
"""

from benchmarks import roofline


def read(run):
    if run.dry:  # a CPU rehearsal has no peak to be a share of
        return None
    win = run.window
    wall = win["end"] - win["start"]
    iters = sum(s.iterations for s in win["solves"])
    host = run.state["shape"]
    least, _bound = roofline.product_min_seconds(
        host["nnz"], host["n_rows"], host["n_features"] + 1,
        roofline.peaks(run.device_kind))
    products = int(run.cfg["products_per_iteration"])
    # The starting value+gradient of each solve is needed work too.
    needed = (iters * products + 2 * len(win["solves"])) * least
    return 100.0 * needed / wall if wall > 0 and iters else None
