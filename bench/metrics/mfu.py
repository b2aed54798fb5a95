"""Model FLOP utilization of the traced window, in percent: the model
FLOPs per token (``bench/flops/<family>.py``, no recomputation counted)
times the traced window's tokens per second, over the chips' summed
bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    if not ctx["tokens_per_s"]:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
