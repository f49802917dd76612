"""Mean idle gap between consecutive programs on a chip (device trace, ms).

Layer serve.dispatch: from the end of one batch's program to the start of
the next on the same chip, averaged over the traced slice and the chips.
Moves `clouds_per_s`.
"""

from benchlib import xtrace


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    if ctx.trace is None:
        return None
    gaps = xtrace.module_gaps_s(ctx.trace)
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
