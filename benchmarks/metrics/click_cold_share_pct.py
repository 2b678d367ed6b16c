"""The cold band's kernel's device seconds over all busy device seconds of
the traced window."""

from benchmarks.metrics import _click


def read(run):
    seconds = _click.cold_seconds(run)
    return None if not seconds else 100.0 * seconds / run.trace.busy_s
