"""quantize_pack_roofline: the least time the traced rounds' uplink
encodes need at the HBM peak (work.encode_bytes: each participating
client's float32 payload read, its int8 codes and per-row scales
written) over the summed device time of the quantize-pack kernel's
events.

The trace names a Pallas kernel after the jitted function that holds it
(``%chunk_step.12 = s8[R,2048]... custom-call(...),
custom_call_target="tpu_custom_call"``): the encode is the one whose
result is int8 codes (uint8 nibble pairs for int4)."""
from benchmarks.chip import trace, work

KERNEL = trace.matcher([r"^%\S+ = [su]8\[\d+,\d+\]\S* custom-call\(.*"
                        r"custom_call_target=\"tpu_custom_call\""])
CODE_BYTES = {"qint8": 1.0, "qint4": 0.5}


def read(ctx):
    t = ctx.trace.op_seconds(KERNEL)
    if t <= 0 or ctx.codec not in CODE_BYTES:
        return None
    nbytes = sum(work.encode_bytes(ctx.unit_leaves, s, CODE_BYTES[ctx.codec])
                 for s in ctx.sels)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / t
