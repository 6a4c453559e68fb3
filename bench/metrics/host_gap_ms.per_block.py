"""Mean idle time of the chip per block outside the block's own scan: the
wait for the host's dispatch, metrics fetch and eval at block boundaries
(``devtrace.TraceView.boundary_gaps_s``)."""


def read(ctx):
    gaps = ctx.view.boundary_gaps_s()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
