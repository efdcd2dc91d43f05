"""Share of its roofline that the compressed wire's codec kernels reach:
the least time of the codec work of the traced window's rounds over the
device time of the ops named ``quant_gossip_*`` (the quantize and
dequantize-accumulate kernels), averaged over the chips.

The least time of one round on one chip is the larger of its operations
over the bf16 peak and its bytes over the HBM peak, counted from the
parameter shapes and the wire alone (:func:`codec_cost`), the same
whatever implements the round.  Moves train_tokens_per_s."""

import numpy as np

from bench import families
from bench.reference import choco, drdsgd

KERNELS = ("quant_gossip_quantize", "quant_gossip_dequant_acc")


def codec_cost(cfg: dict, job: dict) -> tuple[float, float]:
    """(operations, bytes) of one round's codec work on one node.

    Per leaf of d elements in n scale blocks, with M neighbours sending:
    quantize the f32 innovation (read 4d bytes, write d int8 and 4n of
    scales; |x|, the block maximum, x / s, + u and the floor: 5 operations
    an element), and fold the M received payloads into one f32 accumulator
    (read M (d + 4n) bytes, read and write the accumulator once, 8d; a
    scale, a weight and an add: 3 operations an element of each)."""
    k = job["nodes"]
    w = drdsgd.metropolis(job["graph"], k)
    m = max(int(np.count_nonzero(w[i])) - 1 for i in range(k))
    ops = nbytes = 0.0
    for shape in families.load(cfg).shapes(cfg).values():
        d = int(np.prod(shape))
        n = -(-d // choco.block_len(d, job["block_d"]))
        ops += 5.0 * d + 3.0 * m * d
        nbytes += 4.0 * d + d + 4.0 * n + m * (d + 4.0 * n) + 8.0 * d
    return ops, nbytes


def read(ctx):
    if ctx.kind != "train" or ctx.job.get("compress") != "int8" \
            or not ctx.counts.get("steps"):
        return None
    dev = []
    for d in ctx.devices:
        o = ctx.trace.ops(d, ctx.window)
        dev.append(sum(t for t, n, op in zip(o["dur"], o["name"], o["tf_op"])
                       if any(k in n or k in op for k in KERNELS)))
    measured = float(np.mean(dev)) / 1e9
    if measured <= 0:
        return None
    ops, nbytes = codec_cost(ctx.cfg, ctx.job)
    least = max(ops / ctx.peaks["bf16_flops"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.counts["steps"] / measured
