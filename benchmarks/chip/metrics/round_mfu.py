"""round_mfu: the FLOPs the traced rounds' selections require (work.py:
every unit's forward pass, the trained units' weight gradients, the
input gradients down to each client's lowest trained unit) over the
traced window times the chips times each chip's bf16 peak.  The round
step's matmuls run at DEFAULT precision, one bf16 pass."""
from benchmarks.chip import work


def read(ctx):
    if not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    flops = sum(work.round_flops(ctx.macs, s, ctx.examples_per_client)
                for s in ctx.sels)
    peak = ctx.trace.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / peak
