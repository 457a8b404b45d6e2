"""Model FLOP/s utilization of the round: forward + backward FLOPs of every
sample the clients trained in the window (``counts.resnet18s_train_flops``),
over the window, over the chip's bf16 peak."""

import counts


def read(ctx, summary, res):
    samples = ctx.facts["batches"] * ctx.facts["batch_size"]
    if not samples:
        return None
    c = ctx.config
    flops = samples * counts.resnet18s_train_flops(
        width=c["width"], classes=c["classes"], hw=c["image"][0], in_ch=c["image"][2])
    return 100.0 * flops / ctx.window_s / counts.peaks(ctx.device_kind)["flops_bf16"]
