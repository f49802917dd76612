"""Device time per program of the SA grouping stages (ms).

Layer core.grouping: the ops under a `sa{i}/group` scope of the model, the
remap of lattice slots to cloud indices, the neighbourhood feature gathers
and the masked max-pool, found through the program's scope map. Moves
`clouds_per_s`.
"""

from benchlib import scopes


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    return scopes.stage_ms(ctx, "group", "group_ms")
