"""Operations a round requires (``bench/flops.py``, from shapes and the
algorithm) times the traced window's rounds per second, over the chips'
bf16 matmul peak (``bench/peaks.json``)."""


def read(ctx):
    rate = ctx.rounds / ctx.view.window_s()
    peak = ctx.peaks[ctx.peaks["matmul_peak"]]
    return 100.0 * ctx.round_flops * rate / (ctx.chips * peak)
