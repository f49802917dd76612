"""Least time of the traced `pc2im_sc_matmul` calls over their device time (%).

Layer kernels.sc_matmul. The least time of a call is the larger of its operations
over the compute peak and its bytes over HBM bandwidth (`benchlib.work`);
the bound is printed on standard error. One call per linear that the
configuration's program adapter lists. Moves `clouds_per_s`.
"""

import sys

from benchlib import work, xtrace

KERNEL = "pc2im_sc_matmul"


def read(ctx):
    """The metric from a traced run's context, or None where nothing was traced."""
    if ctx.trace is None:
        return None
    events = [e.dur * 1e-9 for e in xtrace.kernel_events(ctx.trace, KERNEL)]
    calls = work.sc_matmul_calls(ctx.program.linear_shapes(ctx.model), ctx.batch, ctx.quant)
    share = work.roofline_pct(calls, events, ctx.peaks)
    if share is None:
        return None
    print(f"sc_matmul_roofline: bound {share[1]}, {len(events)} {KERNEL} events", file=sys.stderr)
    return share[0]
