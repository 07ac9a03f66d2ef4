"""Pallas TPU fused masked participation-weighted FedAvg.

THE paper op: ``new_u = global_u + Σ_c w_c·sel_cu·Δ_cu / Σ_c w_c·sel_cu``
fused over client-stacked deltas.  ops.py packs each freeze unit's
params into tile rows and precomputes the per-tile client weight row
``wm[t, c] = w_c · sel_{c, unit(t)}`` (masks are per-unit constants, so
they collapse from (C, N) floats to (T, C)); the kernel then fuses the
weighted client reduction, the denominator guard, and the global add in
one VMEM pass — one HBM read of the deltas instead of the 3–4 passes the
unfused jnp version takes.

Grid: (n_tiles / ROW_BLOCK,).  Blocks: global (ROW_BLOCK, tile), deltas
(ROW_BLOCK, C, tile), weights (ROW_BLOCK, C, 1).  The TPU compiler takes
a block whose last two dims are multiples of (8, 128) or whole array
dims, so ``ops.build_agg_plan`` pads the tile rows to a multiple of
ROW_BLOCK and the weights carry C on the sublane axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


ROW_BLOCK = 8      # tile rows per grid step (f32 sublane count)


def _agg_kernel(g_ref, d_ref, w_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)            # (R, tile)
    d = d_ref[...].astype(jnp.float32)            # (R, C, tile)
    w = w_ref[...].astype(jnp.float32)            # (R, C, 1)
    denom = w.sum(axis=1)                         # (R, 1)
    num = (w * d).sum(axis=1)                     # (R, tile)
    upd = jnp.where(denom > 0, num / jnp.maximum(denom, 1e-9), 0.0)
    o_ref[...] = (g + upd).astype(o_ref.dtype)


def masked_agg(global_tiled, deltas_tiled, weights_tiled, *,
               interpret=None):
    """global (T, tile); deltas (T, C, tile); weights (T, C) -> (T, tile).

    ``T`` must be a multiple of :data:`ROW_BLOCK` (``ops`` pads it).
    ``interpret=None`` resolves from the backend (compiled on TPU/GPU,
    interpreter on CPU) — see ``kernels.resolve_interpret``.
    """
    interpret = resolve_interpret(interpret)
    t, tile = global_tiled.shape
    c = deltas_tiled.shape[1]
    rb = ROW_BLOCK
    if t % rb:
        raise ValueError(f"masked_agg: {t} tile rows is not a multiple of "
                         f"ROW_BLOCK={rb}")
    return pl.pallas_call(
        _agg_kernel,
        grid=(t // rb,),
        in_specs=[
            pl.BlockSpec((rb, tile), lambda i: (i, 0)),
            pl.BlockSpec((rb, c, tile), lambda i: (i, 0, 0)),
            pl.BlockSpec((rb, c, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, tile), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, tile), global_tiled.dtype),
        interpret=interpret,
        name="masked_agg",
    )(global_tiled, deltas_tiled, weights_tiled[:, :, None])
