"""``game_data_ready_s`` on the cell with two random effects: wall seconds of
the one ``GameEstimator.build_coordinates`` (three coordinates), ended by
``block_until_ready`` on every leaf, the passive rows too."""

from benchmarks.metrics.data_ready_s import read  # noqa: F401
