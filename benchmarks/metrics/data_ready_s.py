"""Wall seconds of the program's own path from host CSR arrays (already in
memory) to a ``GlmData`` resident on the device in the layout the solver
reads: one call of ``make_glm_data``, ended by ``block_until_ready`` on its
leaves.  Measured once per run, inside set-up."""


def read(run):
    return run.spans.get("data_ready")
