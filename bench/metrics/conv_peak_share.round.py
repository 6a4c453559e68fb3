"""The convolutions' share of the chips' bf16 matmul peak
(``bench/peaks.json``), in percent: the convolution operations a round
requires, over the device self time they took, over the peak.

The operations are ``bench/flops.py``'s, restricted to the conv layers
(``F`` forward operations per sample): per client ``3 * F * K * B`` in
local training and ``(3 * S + 1) * 3 * F * n`` in the 3SFC encode (``S``
steps that differentiate through an evaluation at synthetic batch ``n``,
and one more evaluation), times ``N`` clients. The time is the block
executable's self time per round under ``cnn.conv`` inside
``fl.local_train`` or ``fl.encode``. None where the program names no
``cnn.conv`` or the block's HLO text names too few of its operations."""
from bench import flops

SCOPES = ("cnn.conv", "fl.local_train", "fl.encode")


def conv_flops(config, traffic) -> int:
    """Convolution operations a round requires."""
    weighted = [L["kind"] for L in config["layers"]
                if L["kind"] in ("dense", "conv")]
    f = sum(ops for kind, ops in zip(weighted, flops.layer_flops(config))
            if kind == "conv")
    per_client = 3 * f * traffic["local_steps"] * traffic["batch"]
    if traffic["strategy"] == "threesfc":
        per_client += (3 * traffic["syn_steps"] + 1) * 3 * f \
            * traffic["syn_batch"]
    return traffic["clients"] * per_client


def read(ctx):
    v = ctx.view
    if SCOPES[0] not in v.scope_names():
        return None
    conv, phases, either = (v.scope_s(SCOPES[0]), v.scope_s(*SCOPES[1:]),
                            v.scope_s(*SCOPES))
    if either is None:
        return None
    seconds = (conv + phases - either) / ctx.rounds  # conv inside a phase
    if seconds <= 0:
        return None
    peak = ctx.peaks[ctx.peaks["matmul_peak"]]
    return 100.0 * conv_flops(ctx.config, ctx.traffic) / (
        seconds * ctx.chips * peak)
