"""A plan's items as one digest, to pin a plan item for item across a change
of the planner without writing its hundreds of ops out: every fused run's
tile, frame and ops in order (an op's kind, qubits and numbers; a channel op
its kind of CHANNEL and qubits alone, so that a change of a channel's
lowering is pinned apart from the plan around it), every other item by type
and qubits."""

import hashlib

import numpy as np

from quest_tpu import planner

_CHANNELS = ("kraus1", "kraus2", "krausn", "depol")


def _flat(x):
    """Nested tuples / matrices / numbers as a flat, canonical string."""
    if hasattr(x, "arr"):
        x = x.arr
    if isinstance(x, np.ndarray):
        return "[" + ",".join(repr(complex(v)) for v in x.reshape(-1)) + "]"
    if isinstance(x, (tuple, list)):
        return "(" + ",".join(_flat(v) for v in x) + ")"
    return repr(x)


def item_text(item) -> str:
    if isinstance(item, planner.PallasRun):
        ops = []
        for op in item.ops:
            if op[0] in _CHANNELS:
                from quest_tpu.ops.pallas_gates import op_dense_targets

                ops.append("channel" + _flat(tuple(op_dense_targets(op))))
            else:
                ops.append(_flat(op))
        return (f"run tile={item.tile_bits} load={item.load_swap_k}@"
                f"{item.load_swap_hi} store={item.store_swap_k}@"
                f"{item.store_swap_hi} seg={item.seg} " + ";".join(ops))
    if isinstance(item, planner.FrameSwap):
        return f"swap tile={item.tile_bits} k={item.k}@{item.hi}"
    if isinstance(item, (planner.FusedBlock, planner.DiagBlock)):
        return f"{type(item).__name__} {tuple(item.qubits)}"
    return f"raw {getattr(item[0], '__name__', item[0])}"


def plan_digest(plan) -> str:
    text = "\n".join(item_text(i) for i in plan.items)
    return f"{len(plan.items)}:" + hashlib.sha256(text.encode()).hexdigest()[:16]
