"""Share of the block executable's device self time spent in operations
under no named scope of the program (``devtrace.TraceView.unscoped_s``
over ``block_self_s``), in percent."""


def read(ctx):
    rest, block = ctx.view.unscoped_s(), ctx.view.block_self_s()
    return None if rest is None or not block else 100.0 * rest / block
