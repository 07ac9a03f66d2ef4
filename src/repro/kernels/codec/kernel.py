"""Fused stochastic-rounding quantize-pack Pallas kernel.

Per slot row: absmax scale, stochastic round (``floor(x/scale + u)``
with pre-drawn uniforms) and bit-pack — int8 rows stay one byte per
element, int4 rows pack two nibbles per byte.

The per-row absmax, scale and reciprocal are one XLA reduction in the
wrapper, written exactly as ``ref.py`` writes them; the kernel does the
elementwise round and pack.  So no division runs inside the kernel, and
the compiled kernel matches the jnp reference bitwise.  The uniforms are
generated *outside* the kernel too (``jax.random.uniform`` on a key
derived in the round step).

Layout: each row is cut into column tiles of ``COL_BLOCK`` lanes (fewer
for short rows, always a multiple of 128), so a row of any width becomes
``per`` tile rows of one ``(n, cb)`` array, padded to ``ROW_BLOCK`` tile
rows per grid step — the block shape the TPU compiler accepts for f32
inputs and int8/uint8 outputs alike.  int4 packs the even and odd
elements of a row, which the wrapper de-interleaves so that the kernel
reads two lane-aligned streams.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

ROW_BLOCK = 32     # tile rows per grid step: int8's native (32, 128) tile
COL_BLOCK = 2048   # lanes per tile row (multiple of 128)


def row_scales(x, qmax):
    """Per-row absmax scale and its guarded reciprocal, ``(R,)`` each.

    ``scale = absmax * (1/qmax)`` (reciprocal multiply, not division):
    XLA rewrites division-by-constant into reciprocal multiply when
    compiling, which is not exactly rounded — using the multiply form
    everywhere keeps kernel == eager jnp reference bitwise.
    """
    absmax = jnp.max(jnp.abs(x), axis=1)
    scale = absmax * (1.0 / qmax)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    return scale, inv


def _round(x, u, inv, qmax):
    return jnp.clip(jnp.floor(x * inv + u), -qmax, qmax)


def _q8_kernel(x_ref, u_ref, inv_ref, q_ref):
    q = _round(x_ref[...], u_ref[...], inv_ref[...], 127.0)
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)


def _q4_kernel(xe_ref, ue_ref, xo_ref, uo_ref, inv_ref, q_ref):
    inv = inv_ref[...]
    lo = _round(xe_ref[...], ue_ref[...], inv, 7.0).astype(jnp.int32) + 8
    hi = _round(xo_ref[...], uo_ref[...], inv, 7.0).astype(jnp.int32) + 8
    q_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


def _round_up(n, m):
    return -(-n // m) * m


def quantize_pack(x, u, bits, *, interpret=None):
    """Quantize-pack rows of ``x`` with per-row absmax scales.

    Args:
      x: ``(R, P)`` float32 rows to quantize (one scale per row).
      u: ``(R, P)`` uniforms in ``[0, 1)`` for stochastic rounding.
      bits: 8 (int8 bytes) or 4 (two nibbles per uint8 byte).

    Returns:
      ``(packed, scale)`` — packed ``(R, P)`` int8 for 8-bit or
      ``(R, ceil(P/2))`` uint8 for 4-bit, and ``(R,)`` float32 scales.
    """
    if bits not in (8, 4):
        raise ValueError(f"quantize_pack: bits must be 8 or 4, got {bits}")
    interpret = resolve_interpret(interpret)
    r, p = x.shape
    if bits == 4 and p % 2:
        pad = [(0, 0), (0, 1)]
        x = jnp.pad(x, pad)
        u = jnp.pad(u, pad)
    scale, inv = row_scales(x, 127.0 if bits == 8 else 7.0)
    if bits == 8:
        kernel, q_dtype, streams = _q8_kernel, jnp.int8, [x, u]
    else:
        kernel, q_dtype = _q4_kernel, jnp.uint8
        streams = [x[:, 0::2], u[:, 0::2], x[:, 1::2], u[:, 1::2]]
    w = streams[0].shape[1]                     # packed columns per row
    cb = min(COL_BLOCK, _round_up(w, 128))
    per = _round_up(w, cb) // cb                # tile rows per row
    n = _round_up(r * per, ROW_BLOCK)

    def tiles(a):
        a = jnp.pad(a, ((0, 0), (0, per * cb - w))).reshape(r * per, cb)
        return jnp.pad(a, ((0, n - r * per), (0, 0)))

    inv_t = jnp.pad(jnp.repeat(inv, per), (0, n - r * per))[:, None]
    block = pl.BlockSpec((ROW_BLOCK, cb), lambda i: (i, 0))
    packed = pl.pallas_call(
        kernel,
        grid=(n // ROW_BLOCK,),
        in_specs=[block] * len(streams)
        + [pl.BlockSpec((ROW_BLOCK, 1), lambda i: (i, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n, cb), q_dtype),
        interpret=interpret,
        name="quantize_pack",
    )(*[tiles(a) for a in streams], inv_t)
    packed = packed[:r * per].reshape(r, per * cb)[:, :w]
    return packed, scale
