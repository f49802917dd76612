"""Device time per program of the FP stages' 3-NN searches (ms).

Layer core.propagation: the ops under a `fp{i}/knn` scope of the model, the
brute-force 3-nearest-neighbour search of each feature-propagation stage,
found through the program's scope map. Moves `clouds_per_s`.
"""

from benchlib import scopes


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    return scopes.stage_ms(ctx, "knn", "knn_ms")
