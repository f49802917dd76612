"""Whole step's share of the chips' bf16 peak (host clock and work functions, %).

Model FLOPs per cloud (every linear that the configuration's program
adapter lists, `ctx.program.linear_shapes`, at its row counts) times the
clouds answered per second of the window, over chips times the bf16 peak.
Layer core.accelerator. Moves `clouds_per_s`.
"""

from benchlib import work


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    if not ctx.peaks or ctx.clouds_per_s <= 0:
        return None
    flops = work.model_flops_per_cloud(ctx.program.linear_shapes(ctx.model)) * ctx.clouds_per_s
    return 100.0 * flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
