"""Metric-name lint — thin compatibility shim.

The implementation moved to :mod:`photon_ml_tpu.analysis.rules_registry`
as the ``metric-naming`` rule of the project-wide invariant checker
(``python -m photon_ml_tpu.analysis --check``); this module re-exports
the old surface so ``python -m photon_ml_tpu.telemetry --lint-metrics``
and existing imports keep working unchanged.
"""

from __future__ import annotations

from photon_ml_tpu.analysis.engine import SourceTree
from photon_ml_tpu.analysis.rules_registry import (  # noqa: F401
    LEGACY_NAMES,
    SUBSYSTEMS,
    UNITS,
    lint_name,
    lint_source,
    scan_tree,
)


def scan_source(roots=None) -> list[tuple[str, str, str, int]]:
    """Old entry point: ``(name, kind, relpath, lineno)`` hits over the
    default root (the package) or explicit ``roots``."""
    return scan_tree(SourceTree(roots=roots))


__all__ = [
    "LEGACY_NAMES",
    "SUBSYSTEMS",
    "UNITS",
    "lint_name",
    "lint_source",
    "scan_source",
]
