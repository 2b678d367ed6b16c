"""Host seconds of the column permutation inside the set-up's
``layout.build``: its ``layout.col_perm`` child.  All the children
(``.canonicalize`` and ``.dense_split`` too) go to the result line as
``layout_phases``."""

from benchmarks.metrics import _setup


def read(run):
    phases = _setup.layout_phases(run)
    return None if phases is None else phases.get("layout.col_perm")
