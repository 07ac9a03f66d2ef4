"""masked_agg_roofline: the least time the traced rounds' FedAvg reduces
need at the HBM peak (work.reduce_bytes: each participating client's
delta of each unit it trained, the unit's old and new value) over the
summed device time of the fused masked-FedAvg kernel's events.

The trace names a Pallas kernel after the jitted function that holds it
(``%round_step.1 = f32[T,2048]... custom-call(...),
custom_call_target="tpu_custom_call"``): the reduce is the one whose
result is a float32 tile matrix."""
from benchmarks.chip import trace, work

KERNEL = trace.matcher([r"^%\S+ = f32\[\d+,\d+\]\S* custom-call\(.*"
                        r"custom_call_target=\"tpu_custom_call\""])


def read(ctx):
    t = ctx.trace.op_seconds(KERNEL)
    if t <= 0:
        return None
    nbytes = sum(work.reduce_bytes(ctx.unit_leaves, s) for s in ctx.sels)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / t
