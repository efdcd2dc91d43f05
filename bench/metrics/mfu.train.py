"""mfu.train: model FLOPs of the traced window's training steps over the
window's host time, the chips and the chip's bf16 peak (float32 matmuls at
default precision are one bf16 pass on the TPU).  Moves train_tokens_per_s."""

from bench import families


def read(ctx):
    if ctx.kind != "train" or not ctx.counts.get("tokens"):
        return None
    per_token = families.load(ctx.cfg).train_flops_per_token(ctx.cfg, ctx.job["seq_len"])
    flops = per_token * ctx.counts["tokens"]
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops"])
