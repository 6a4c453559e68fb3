"""Device self time per round of the block executable's operations under
the strategy's encode scope ``fl.encode`` (3SFC synthesis, its kernels,
a codec's pack; ``devtrace.TraceView.scope_s``), in milliseconds."""

SCOPES = ("fl.encode",)


def read(ctx):
    s = ctx.view.scope_s(*SCOPES)
    return None if s is None else 1e3 * s / ctx.rounds
