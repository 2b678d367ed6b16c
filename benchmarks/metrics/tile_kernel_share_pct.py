"""The tile kernel's device time over all device busy time in the window;
what is left is XLA (loss, line search, two-loop recursion, CG vectors)."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_durations_s or t.busy_s <= 0:
        return None
    return 100.0 * sum(t.kernel_durations_s) / t.busy_s
