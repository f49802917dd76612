"""Device time per program of the SA median partitions (ms).

Layer core.partition: the ops under a `sa{i}/partition` scope of the model,
the median sort into tiles and the gather of tile coordinates, found
through the program's scope map. Moves `clouds_per_s`.
"""

from benchlib import scopes


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    return scopes.stage_ms(ctx, "partition", "partition_ms")
