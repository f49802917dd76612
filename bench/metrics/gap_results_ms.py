"""Mean time of each device gap spent on the finished batch's results (ms).

Layer serve.dispatch: from the end of a program on the chip to the end of
the first `batch.complete` span after it (the result wait, the copy to the
host and the completion of every client future), clamped into the gap
before the next program. With `gap_inputs_ms` it sums to the gap mean that
`dispatch_gap_ms` reads. Moves `clouds_per_s`.
"""

from benchlib import scopes


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    return scopes.gap_part_ms(ctx, 0, "gap_results_ms")
