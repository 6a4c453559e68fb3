"""Device busy time of the traced window per round (averaged over the
cell's chips), in milliseconds."""


def read(ctx):
    return 1e3 * ctx.view.busy_s() / ctx.rounds
