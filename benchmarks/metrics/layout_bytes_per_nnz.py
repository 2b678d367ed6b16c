"""Bytes of the resident feature matrix (sum of its leaves' nbytes) per
valued non-zero of the data."""


def read(run):
    nbytes = run.state.get("feature_bytes")
    return nbytes / run.state["shape"]["nnz"] if nbytes else None
