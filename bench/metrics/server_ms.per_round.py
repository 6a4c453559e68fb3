"""Device self time per round of the block executable's operations under
the server's scopes: ``fl.gather`` (a sharded fan-out's gather),
``fl.aggregate`` and ``fl.update`` (``devtrace.TraceView.scope_s``), in
milliseconds."""

SCOPES = ("fl.gather", "fl.aggregate", "fl.update")


def read(ctx):
    s = ctx.view.scope_s(*SCOPES)
    return None if s is None else 1e3 * s / ctx.rounds
