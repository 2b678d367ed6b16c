"""The per-update train metric's share of the device's busy time: device
seconds of the evaluation programs (``jit_device_auc``,
``jit_device_pointwise_metric``: ``evaluation/device.py``) that
``GameEstimator.fit_coordinates`` runs after every coordinate update, over
the traced window's busy seconds."""

from benchmarks.metrics import re_solve_share_pct


def read(run):
    return re_solve_share_pct.read(run, mark="jit_device_")
