"""Operations a round requires (``bench/flops.py``, from shapes and the
algorithm) times the traced window's rounds per second, over the chips'
bf16 matmul peak (``bench/peaks.json``); nothing where the configuration's
family brings no operation count."""


def read(ctx):
    if ctx.round_flops is None:
        return None
    rate = ctx.rounds / ctx.view.window_s()
    peak = ctx.peaks[ctx.peaks["matmul_peak"]]
    return 100.0 * ctx.round_flops * rate / (ctx.chips * peak)
