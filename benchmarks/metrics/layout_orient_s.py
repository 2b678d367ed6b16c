"""Host seconds of laying the entries out for the kernel inside the
set-up's ``layout.build``: its ``layout.orient`` children, both sides
(four where a spill forced the rebuild)."""

from benchmarks.metrics import _setup


def read(run):
    phases = _setup.layout_phases(run)
    return None if phases is None else phases.get("layout.orient")
