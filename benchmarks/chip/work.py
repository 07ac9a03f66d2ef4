"""The work a round requires, counted from shapes and selection rows.

These counts are of the work, not of any implementation: no tiles, no
padding, no packing copies, no rows of clients that trained nothing of a
unit.  So a later change that removes such overhead leaves them as they
are, and a share of a peak computed from them cannot pass 100 %.

``unit_leaves`` maps each freeze unit to the element counts of its
leaves; ``sel`` is a round's ``(C, U)`` 0/1 selection matrix.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

F32 = 4


def unit_leaves(shapes, units: Sequence[str]) -> List[List[int]]:
    """Element counts of each unit's leaves, from a ``{unit: {leaf:
    shape}}`` tree of shapes (``jax.eval_shape`` of the weights)."""
    import jax
    out = []
    for u in units:
        leaves = jax.tree_util.tree_leaves(shapes[u])
        out.append([int(np.prod(leaf.shape)) for leaf in leaves])
    return out


def step_flops(macs: Sequence[int], sel_row) -> float:
    """FLOPs one example needs in one local step of a client that trains
    the units ``sel_row`` marks: every unit's forward pass, the weight
    gradient of each trained unit, and the gradient with respect to the
    input of every unit above the lowest trained one (the backward pass
    stops there).  ``macs`` are the multiply-accumulates per example of
    each unit, in forward order."""
    sel = np.asarray(sel_row) > 0
    lowest = int(np.argmax(sel)) if sel.any() else len(macs)
    total = 0
    for u, m in enumerate(macs):
        total += 2 * m
        if sel[u]:
            total += 2 * m
        if u > lowest:
            total += 2 * m
    return float(total)


def round_flops(macs: Sequence[int], sel, examples_per_client: int) -> float:
    """FLOPs of one round: each client's ``examples_per_client`` (local
    steps x batch) examples at its own selection."""
    return float(sum(examples_per_client * step_flops(macs, row)
                     for row in np.asarray(sel)))


def reduce_bytes(unit_leaves: List[List[int]], sel) -> float:
    """HBM bytes of one masked FedAvg reduce: read each participating
    client's float32 delta of each unit it trained, read the unit's
    global value and write its new value (units nobody trained are left
    as they are)."""
    sel = np.asarray(sel) > 0
    total = 0
    for u, leaves in enumerate(unit_leaves):
        n = int(sel[:, u].sum())
        if n:
            total += F32 * sum(leaves) * (n + 2)
    return float(total)


def encode_bytes(unit_leaves: List[List[int]], sel, code_bytes: float = 1.0
                 ) -> float:
    """HBM bytes of one round's uplink encode: read each participating
    client's float32 payload of each unit it trained, write its codes
    (``code_bytes`` per element: 1 for int8, 0.5 for int4) and one float32
    scale per row (one row per leaf)."""
    sel = np.asarray(sel) > 0
    total = 0.0
    for u, leaves in enumerate(unit_leaves):
        n = int(sel[:, u].sum())
        total += n * sum(p * (F32 + code_bytes) + F32 for p in leaves)
    return total


# wire bytes per leaf of P float32 elements, by codec: the payload as it
# crosses the network (one scale per row for the quantising codecs)
WIRE = {
    "none": lambda p: F32 * p,
    "qint8": lambda p: p + F32,
    "qint4": lambda p: (p + 1) // 2 + F32,
}


def uplink_bytes(unit_leaves: List[List[int]], sel, codec: str) -> float:
    """Bytes the round's clients upload: every trained unit of every
    client, encoded as ``codec`` sends it."""
    wire = WIRE[codec]
    per_unit = np.asarray([sum(wire(p) for p in leaves)
                           for leaves in unit_leaves], np.float64)
    return float((np.asarray(sel, np.float64) > 0).sum(0) @ per_unit)

