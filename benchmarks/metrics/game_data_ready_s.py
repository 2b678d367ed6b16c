"""Wall seconds of the program's own path from host arrays (already in
memory) to coordinates whose data is resident on the device: one call of
``GameEstimator.build_coordinates`` (the fixed effect's ``make_glm_data``,
the random effect's grouping and placement), ended by ``block_until_ready``
on every leaf.  Measured once per run, inside set-up."""

from benchmarks.metrics.data_ready_s import read  # noqa: F401
