"""Device self time per round of the block executable's operations under
the engine batcher's scope ``fl.batch`` (``devtrace.TraceView.scope_s``),
in milliseconds."""

SCOPES = ("fl.batch",)


def read(ctx):
    s = ctx.view.scope_s(*SCOPES)
    return None if s is None else 1e3 * s / ctx.rounds
