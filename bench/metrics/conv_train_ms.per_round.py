"""Device self time per round of the block executable's operations under
both the clients' local training scope ``fl.local_train`` and the
convolutions' scope ``cnn.conv`` (``models/layers.CONV_SCOPE``): the
per-client convolutions of local training, forward and backward, in
milliseconds. None where the program names no ``cnn.conv`` (a program
without the scope) or the block's HLO text names too few of its
operations (``devtrace.TraceView.scope_s``)."""

SCOPES = ("fl.local_train", "cnn.conv")


def read(ctx):
    v = ctx.view
    if SCOPES[1] not in v.scope_names():
        return None
    a, b, either = v.scope_s(SCOPES[0]), v.scope_s(SCOPES[1]), v.scope_s(*SCOPES)
    if either is None:
        return None
    return 1e3 * (a + b - either) / ctx.rounds       # under both
