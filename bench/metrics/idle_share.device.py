"""Share of the traced window in which no operation runs on the chip
(averaged over the cell's chips): 1 - busy / window."""


def read(ctx):
    return 100.0 * ctx.view.idle_share()
