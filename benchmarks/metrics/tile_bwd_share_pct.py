"""The backward products' share of the tile kernel's device time: seconds
under the instructions named ``_tiled_apply_bwd*`` over seconds under all
named ``_tiled_apply*``.  Nothing where the kernel's two orientations share
one name."""

from benchmarks import trace as trace_mod

BACKWARD_MARK = trace_mod.KERNEL_MARK + "_bwd"


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel = [(n, s) for n, s in t.device_ops
              if n.startswith(trace_mod.KERNEL_MARK)]
    backward = sum(s for n, s in kernel if n.startswith(BACKWARD_MARK))
    total = sum(s for _n, s in kernel)
    return 100.0 * backward / total if backward > 0 else None
