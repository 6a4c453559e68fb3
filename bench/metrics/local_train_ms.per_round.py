"""Device self time per round of the block executable's operations under
the clients' local training scope ``fl.local_train``
(``devtrace.TraceView.scope_s``), in milliseconds."""

SCOPES = ("fl.local_train",)


def read(ctx):
    s = ctx.view.scope_s(*SCOPES)
    return None if s is None else 1e3 * s / ctx.rounds
