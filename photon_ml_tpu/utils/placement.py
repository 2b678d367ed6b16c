"""Host arrays to the default device, one leaf at a time, saying what each
leaf's copy cost on the host.

``jnp.asarray`` of a host array returns once the copy is queued, so the
seconds spent INSIDE it are host work: a cast, a copy of a leaf that is not
C-contiguous, staging.  The link's seconds show in the one wait at the end
(the caller's ``block_until_ready``).  ``layout.place`` and ``game.place``
report both (docs/telemetry.md "Layer spans"): dispatch against wait is
what tells a host-side copy from the link.  Nothing here blocks.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def place_leaves(tree, prefix: str = ""):
    """``(placed, leaves)``: ``tree`` with every leaf on the default device
    (``jnp.asarray``: a leaf that is resident already passes through), and
    one entry per leaf, in the tree's order: ``path``, ``bytes``,
    ``src_dtype`` and ``dtype`` (host and device), ``contiguous`` (whether a
    host array was C-contiguous; ``None`` for any other leaf) and
    ``dispatch_s`` (host seconds inside the leaf's ``jnp.asarray``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    placed, leaves = [], []
    for path, leaf in flat:
        t0 = time.perf_counter()
        out = jnp.asarray(leaf)
        dispatch_s = time.perf_counter() - t0
        placed.append(out)
        leaves.append({
            "path": prefix + jax.tree_util.keystr(path),
            "bytes": int(out.nbytes),
            "src_dtype": str(getattr(leaf, "dtype", type(leaf).__name__)),
            "dtype": str(out.dtype),
            "contiguous": bool(leaf.flags.c_contiguous)
            if isinstance(leaf, np.ndarray) else None,
            "dispatch_s": dispatch_s,
        })
    return jax.tree_util.tree_unflatten(treedef, placed), leaves
