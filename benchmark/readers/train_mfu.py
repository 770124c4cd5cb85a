"""Model FLOP/s utilization: the operations forward and backward REQUIRE
per token (the family's `train_flops_per_token`: no embedding gather,
causal attention as half, no recomputation) times the tokens per second
per chip of this run's window, over the chip's published bf16 peak."""

from .. import peaks
from . import train_tok_s_chip


def read(ctx, args):
    rate = train_tok_s_chip.read(ctx, args)
    if rate is None or ctx["device"]["platform"] != "tpu":
        return None         # the CPU rehearsal has no peak to share
    flops = ctx["family"].train_flops_per_token(ctx["config"],
                                                ctx["traffic"]["seq"])
    return 100.0 * flops * rate / peaks.peak(ctx["device"]["device_kind"],
                                             "bf16_flops")
