"""Pallas kernel time over device busy time in the traced window."""


def read(ctx):
    if not ctx.view.pallas_ops():
        return None
    return 100.0 * ctx.view.pallas_s() / ctx.view.busy_s()
