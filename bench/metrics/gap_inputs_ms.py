"""Mean time of each device gap spent on the next batch's input (ms).

Layer serve.dispatch: from the end of the first `batch.complete` span after
a program (clamped into the gap) to the start of the next program on the
chip: the replica worker's turn, the copy of the batch to the chip and the
launch. With `gap_results_ms` it sums to the gap mean that
`dispatch_gap_ms` reads. Moves `clouds_per_s`.
"""

from benchlib import scopes


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    return scopes.gap_part_ms(ctx, 1, "gap_inputs_ms")
