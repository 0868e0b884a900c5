"""What a fused run's kernel holds, by kind (``pallas_gates.kernel_op_kind``,
the ``kernel_op_kinds`` field of a pallas plan's ``fusion.plan`` event): the
classification by how an op exchanges partners, and the counts of the benchmark's
kernel cells at their REAL sizes -- plans only, nothing runs. The in-vreg
butterflies (a dense gate on qubits 7-9, whose partner rows lie inside one
(8, 128) vreg) are the count PR 36 went by: 8 in ``sv26.block``'s plan, 4 in
``sv30.block``'s, none in ``density14.block``'s."""

import json
import os

import numpy as np
import pytest

from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.ops import pallas_gates as PG

from .helpers import pallas_runs
from .test_large_register import BENCH, bench  # noqa: F401  (the fixture)

_U = PG.HashableMatrix(np.array([[0.6, 0.8j], [0.8j, 0.6]]))
_T = PG.HashableMatrix(np.diag([1, np.exp(0.25j * np.pi)]))
_K = ((1.0, _U),)

#: one op of every form the kernels take -> its kind
_OPS = {
    "lane_u": (("lane_u", PG.HashableMatrix(np.zeros((3, 128, 128)))),
               "lane_u"),
    "window": (("window", 7, 5, PG.HashableMatrix(np.eye(64))), "window"),
    "diagonal-matrix": (("matrix", 8, (), (), _T), "diag"),
    "grid-diagonal": (("matrix", 25, (3,), (1,), _T), "diag"),
    "parity": (("parity", (0, 8, 25), (), 0.3), "diag"),
    "diagw": (("diagw", (1, 9), (), PG.HashableMatrix(np.ones(4))), "diag"),
    "lane-q0": (("matrix", 0, (), (), _U), "butterfly_lane"),
    "lane-q6-controlled-from-q8": (("matrix", 6, (8,), (1,), _U),
                                   "butterfly_lane"),
    "invreg-q7": (("matrix", 7, (), (), _U), "butterfly_invreg"),
    "invreg-q8": (("matrix", 8, (20,), (0,), _U), "butterfly_invreg"),
    "invreg-q9": (("matrix", 9, (), (), _U), "butterfly_invreg"),
    "rows-q10": (("matrix", 10, (), (), _U), "butterfly_rows"),
    "rows-q18": (("matrix", 18, (7,), (1,), _U), "butterfly_rows"),
    "kraus1": (("kraus1", 8, 15, _K), "kraus"),
    "kraus2": (("kraus2", 0, 1, 14, 15, _K), "kraus"),
    "krausn": (("krausn", (0, 1, 2), (14, 15, 16), _K), "kraus"),
    "depol-one-target": (("depol", (8,), (15,), 4e-3 / 3), "depol"),
    "depol-two-targets": (("depol", (3, 4), (16, 17), 0.16 / 15), "depol"),
}


@pytest.mark.parametrize("case", sorted(_OPS))
def test_kernel_op_kind(case):
    op, kind = _OPS[case]
    assert PG.kernel_op_kind(op) == kind
    assert kind in PG.KERNEL_OP_KINDS
    assert PG.kernel_op_kinds([op, op])[kind] == 2


@pytest.mark.parametrize("q1,q2,kind", [
    (3, 8, "invreg"), (8, 12, "invreg"), (3, 12, "lane"), (7, 9, "invreg"),
    (10, 14, "rows")])
def test_a_swap_counts_once_by_its_exchanges_shapes(q1, q2, kind,
                                                    monkeypatch):
    """A butterfly with two dense targets counts once: ``invreg`` if either
    exchanges inside a vreg, else ``lane`` if either is a lane bit, else
    ``rows`` -- whatever the fold model's prices, which cost it the sum of
    both exchanges."""
    x = PG.HashableMatrix(np.array([[0.0, 1.0], [1.0, 0.0]]))
    one = [PG._op_cost_ms(("matrix", q, (), (), x)) for q in (q1, q2)]
    swap = ("swap", q1, q2, (), ())
    assert PG._op_cost_ms(swap) == pytest.approx(sum(one))
    assert PG.kernel_op_kind(swap) == "butterfly_" + kind
    monkeypatch.setattr(PG, "_BUTTERFLY_MS",
                        {"lane": 0.1, "invreg": 0.2, "rows": 0.9})
    assert PG.kernel_op_kind(swap) == "butterfly_" + kind


def _cell_plan(bench, config, rehearse):  # noqa: F811
    """(the cell's fused circuit, its ``fusion.plan`` event): the builder,
    its arguments and the ``fused`` options of ``benchmark/configs/``."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    args = dict(cfg["circuit"]["args"])
    if rehearse:
        args.update(cfg["rehearse"]["circuit_args"])
    density = cfg["register"] != "statevector"
    circ = Circuit(args["num_qubits"], is_density_matrix=density)
    bench[cfg["circuit"]["builder"]].build(circ, **args)
    telemetry.reset()
    fz = circ.fused(dtype=np.float32, **cfg["fused"])
    events = [e for e in telemetry.events() if e.get("name") == "fusion.plan"
              and e.get("mode") in ("pallas", "pallas_sharded")]
    return fz, events[-1] if events else None


def _counted_by_hand(fz):
    """The kinds of every run of the tape (``fusion.plan_from_tape``),
    folded as ``fused_local_run`` folds them."""
    total = dict.fromkeys(PG.KERNEL_OP_KINDS, 0)
    for run in pallas_runs(fz):
        for op in PG._fold_zone_ops(run.ops, run.tile_bits):
            total[PG.kernel_op_kind(op)] += 1
    return total


#: what each kernel cell's plan holds at its real size: (dense ops on
#: qubits 7-9, ISSUE 36's table; zone dots ``lane_u`` + ``window`` an
#: application). PR 36 changed how the first exchange partners and left the
#: fold model's prices alone, so both are what the plans held before it: a
#: re-pricing moves ops from exact f32 butterflies into bf16x3 dots, and
#: the second number is where that shows
_CELLS = {
    "sv26-f32-random": (8, 5), "sv30-f32-random": (4, 8),
    "density14-channels": (0, 2), "sv20-f32-random": (21, 17),
    "sv31x4-f32-random": (7, 7),
}


@pytest.mark.parametrize("config", sorted(_CELLS))
def test_the_invreg_butterflies_pr36_went_by(bench, config):  # noqa: F811
    """ISSUE 36's table, from the tapes alone (``fusion.plan_from_tape``,
    nothing runs): the cells' kernels hold 8, 4, 0, 21 and 7 dense ops on
    qubits 7-9, and no more zone dots than before PR 36."""
    fz, _ = _cell_plan(bench, config, rehearse=False)
    kinds = _counted_by_hand(fz)
    assert kinds["butterfly_invreg"] == _CELLS[config][0]
    assert kinds["lane_u"] + kinds["window"] == _CELLS[config][1]
    assert kinds["butterfly_lane"] == 0   # the lane zone folds from one


@pytest.mark.parametrize("config", sorted(_CELLS))
def test_plan_event_counts_the_cells_kernel_ops_by_kind(bench, config):  # noqa: F811
    fz, event = _cell_plan(bench, config, rehearse=False)
    kinds = event["kernel_op_kinds"]
    assert tuple(kinds) == PG.KERNEL_OP_KINDS
    assert kinds == _counted_by_hand(fz)
    assert kinds["butterfly_invreg"] == _CELLS[config][0]
    assert (kinds["kraus"] > 0) == (config == "density14-channels")


@pytest.mark.parametrize("config", sorted(_CELLS))
def test_plan_event_kinds_at_rehearsal_size(bench, config):  # noqa: F811
    """The plans ``run.py --rehearse`` makes (12-14 qubits; the density
    cell's 7 are a register of 2^14 amplitudes, a tile of its own): the
    event counts every folded op of every run once, under the seven kinds."""
    fz, event = _cell_plan(bench, config, rehearse=True)
    if not pallas_runs(fz):
        assert event is None or sum(event["kernel_op_kinds"].values()) == 0
        return
    kinds = event["kernel_op_kinds"]
    assert set(kinds) == set(PG.KERNEL_OP_KINDS)
    assert kinds == _counted_by_hand(fz)
    assert sum(kinds.values()) == sum(
        len(PG._fold_zone_ops(r.ops, r.tile_bits)) for r in pallas_runs(fz))


def test_a_double_float_plan_event_counts_its_ops_unfolded(monkeypatch):
    """A double-float route hands its kernels the ops as they are
    (``fused_local_run`` folds nothing there), so the event of a plan made
    for one counts them unfolded: no zone dot."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    circ = Circuit(12)
    for q in range(12):
        circ.hadamard(q)
    telemetry.reset()
    fz = circ.fused(max_qubits=5, pallas=True, dtype=np.float64)
    event = [e for e in telemetry.events()
             if e.get("name") == "fusion.plan"][-1]
    kinds = event["kernel_op_kinds"]
    assert kinds["lane_u"] == kinds["window"] == 0
    assert sum(kinds.values()) == sum(len(r.ops) for r in pallas_runs(fz))
    assert kinds["butterfly_lane"] == 7 and kinds["butterfly_invreg"] == 3


def test_a_dense_plan_event_has_no_kernel_kinds():
    circ = Circuit(10)
    circ.hadamard(0)
    circ.controlledNot(0, 9)
    telemetry.reset()
    circ.fused(max_qubits=5)
    event = [e for e in telemetry.events()
             if e.get("name") == "fusion.plan"][-1]
    assert event["mode"] == "dense" and "kernel_op_kinds" not in event
