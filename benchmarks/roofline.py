"""What the algorithm needs, from shapes alone, and the least time a chip
could take for it.  Nothing here reads the program's layout or its launch
count: the same work reads the same whatever implements it.

A sparse product (``X w`` or ``X^T u``) over ``nnz`` valued entries of a
``rows x cols`` matrix needs, per entry, its column (or row) index within a
tile and its value, and per row and per column one vector element:

    bytes = nnz * (index_bytes + value_bytes) + 4 * (rows + cols)
    ops   = 2 * nnz                     (a multiply and an add)

``index_bytes`` is 2 (an index under 2^16 within a stripe of columns);
``value_bytes`` is 4 (f32), or 0 where the configuration says every value
is 1.  A dense column of ones (the intercept) needs no entry bytes at all.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(known: {sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


def sparse_product_bytes(nnz: int, rows: int, cols: int,
                         unit_values: bool = False) -> int:
    return nnz * (2 + (0 if unit_values else 4)) + 4 * (rows + cols)


def sparse_product_ops(nnz: int) -> int:
    return 2 * nnz


def min_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")


def product_min_seconds(nnz, rows, cols, peak, unit_values=False):
    return min_seconds(sparse_product_ops(nnz),
                       sparse_product_bytes(nnz, rows, cols, unit_values),
                       peak)
