"""Ask the v5e's own compiler, in the sandbox, before any chip time.

Every other Pallas test in tier-1 runs the kernels in INTERPRET mode
(tests/test_pallas.py), which cannot see what Mosaic refuses: a slice not
aligned to the tiling, a kernel over its VMEM budget, an op with no TPU
lowering. libtpu compiles for a chip that is only DESCRIBED
(``jax.experimental.topologies``), so these tests lower the jitted kernel
functions themselves with ``interpret=False`` at the sizes chip_smoke.py
runs -- the fused f32 run of the 26q depth-8 plan (plain and with a folded
frame swap), its per-shard form at the 4-chip local size, a double-float
run at 20q and the 14q density run holding kraus1 + krausn ops -- and
fail on whatever the chip's compiler would raise.
A compile that passes is not a chip run: nothing executes here.

Mosaic compile time grows steeply with the op count of a run (a 24-op
prefix of the 26q plan's first pass took 127 s on the chip's host), so
each case compiles one op of every KIND a real planned run holds after
zone folding (``_one_of_each``): the geometry, DMA ring, swap folding and
op kinds are the plan's own; only the run is shorter -- which also means
the VMEM headroom of the LONGEST run is not what these tests see.

The topology is described inside a module-scoped fixture (never at
import, never in conftest.py, not autouse): only the worker that is handed
this file loads libtpu. All compiles stay in this ONE file and in the
test's own process.
"""

import os
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quest_tpu import environment, fusion
from quest_tpu.circuits import Circuit
from quest_tpu.environment import AMP_AXIS
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops.pallas_df import DF_MAX_OPS, DF_SUBLANES

from .helpers import pallas_runs, shape_register

#: double-float ops compiled (a real chunk's prefix): ~30 s per df op
_DF_OPS = 4


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2, with the persistent compile
    cache off around the module: an AOT compile is written to the cache
    but cannot be read back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or its lock is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """SingleDeviceSharding on one chip of the described host."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as the canonical amps mesh."""
    return Mesh(np.array(topo.devices), (AMP_AXIS,))


def _planned_runs(circ, **fused_kw):
    """The PallasRuns of ``circ.fused(pallas=True, ...)``."""
    return pallas_runs(circ.fused(max_qubits=5, pallas=True,
                                  dtype=np.float32, **fused_kw))


def _cell_layers():
    """The benchmark's ``circuits/random_layers.py``: the library cells' tape."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "random_layers", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "circuits", "random_layers.py"))
    layers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(layers)
    return layers


def _noisy_circuit(n, depth=2):
    """The benchmark's ``circuits/noisy_layers.py`` on a density register:
    ``density15.noise``'s tape (46 gates, a depolarising channel after each)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "noisy_layers", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "circuits", "noisy_layers.py"))
    noisy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(noisy)
    circ = Circuit(n, is_density_matrix=True)
    noisy.build(circ, num_qubits=n, depth=depth, circuit_seed=2026,
                p1=1e-3, p2=1e-2)
    return circ


def _random_circuit(n, depth=8):
    from __graft_entry__ import _random_layers

    circ = Circuit(n)
    _random_layers(circ, n, depth)
    return circ


#: the zone of ``_one_of_each`` a ``matrix`` op of each kind stands for
_ZONE_OF_KIND = {"diag": "diag", "butterfly_lane": "lane",
                 "butterfly_invreg": "invreg", "butterfly_rows": "sublane"}


def _one_of_each(ops_folded, lq, must=()):
    """A short run that still holds every op KIND of the folded run (the
    folded zone dots ``lane_u`` / ``window`` included), a ``matrix`` op of
    each target zone it has (``lane``: lane rotates; ``invreg``: the
    sublane rotates inside a vreg, q in 7..9; ``sublane``: the slice
    exchange of whole vregs; ``diag``; ``grid``: a grid-bit diagonal), and
    the ``must`` ops -- compile time is per op, coverage is per kind.
    One-op kernels at the 4 MiB tile in this sandbox (PR 36): an
    ``invreg`` butterfly 2.7 s, a ``sublane`` one 1.0 s. The slice exchange
    that q in 7..9 took before cost Mosaic 9.2 s for one, 43 s for two,
    102 s for three and 347 s for four of them, which is why these tests
    used to leave the sublane zone out."""
    picked, kinds, zones = list(must), set(), set()
    for op in ops_folded:
        if op in picked:
            continue
        if op[0] == "matrix":
            zone = ("grid" if op[1] >= lq
                    else _ZONE_OF_KIND[PG.kernel_op_kind(op)])
            if zone in zones:
                continue
            zones.add(zone)
        elif op[0] in kinds:
            continue
        kinds.add(op[0])
        picked.append(op)
    return tuple(picked)


def _fused_kw(n, ops, *, planes=2, sublanes=PG._DEF_SUBLANES, local_n=None,
              lk=0, sk=0, lh=None, sh=None, ring=None, must=(), whole=False):
    """``_fused_local_run``'s static arguments as ``fused_local_run``
    would pass them on the TPU (interpret=False), the folded op list cut by
    ``_one_of_each`` unless ``whole`` (double-float runs pass theirs as
    given)."""
    lq = PG.local_qubits(n, sublanes)
    if planes == 4:
        ops_l = tuple(ops)
    else:
        ops_l = PG._fold_zone_ops(ops, lq)
        if not whole:
            ops_l = _one_of_each(ops_l, lq, must)
    return dict(n=n, ops=ops_l, sublanes=sublanes, interpret=False,
                local_n=local_n, load_swap_k=lk, store_swap_k=sk,
                load_swap_hi=lh, store_swap_hi=sh,
                ring_depth=PG.ring_depth_default() if ring is None else ring,
                df_acc=False)


def _compile_chain(one_chip, n, chain, planes=2):
    """Lower + compile a program of chained ``_fused_local_run`` calls
    (one ``_fused_kw`` dict each) for the described chip. The state enters
    as the DONATED (planes, 2^n) PARAMETER, as ``Circuit.compiled`` hands a
    register's amplitudes to its program: the compiler tiles it T(2,128),
    and the kernels read those bytes as they lie (``PG._rows_view``)."""
    def run(amps, shard_index):
        for kw in chain:
            amps = PG._fused_local_run(amps, shard_index, **kw)
        return amps

    amps = jax.ShapeDtypeStruct((planes, 1 << n), jnp.float32,
                                sharding=one_chip)
    si = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(run, donate_argnums=(0,)).lower(amps, si).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == len(chain)
    return compiled


def _compile_fused(one_chip, n, ops, *, planes=2, **kw):
    """One ``_fused_local_run`` alone: see ``_fused_kw``, ``_compile_chain``."""
    return _compile_chain(one_chip, n, [_fused_kw(n, ops, planes=planes, **kw)],
                          planes=planes)


#: an instruction may be as large as the state only if it moves nothing
_NO_TRAFFIC = {"parameter", "bitcast", "custom-call", "tuple",
               "get-tuple-element"}
_HLO_OP = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]+)\]")


def _state_sized_traffic(hlo_text, state_elems):
    """(opcode, instruction name) of every instruction of the compiled
    program whose result is at least a quarter of the state and which is
    not a view of it or a kernel: XLA's relayout ``copy`` /
    ``copy_bitcast_fusion`` of the register, under whatever name."""
    found = []
    for line in hlo_text.splitlines():
        name, eq, rest = line.strip().partition(" = ")
        op = _HLO_OP.search(" " + rest) if eq else None
        if op is None or op.group(1) in _NO_TRAFFIC:
            continue
        result_type = (" " + rest)[:op.start()]
        if any(np.prod([int(d) for d in dims.split(",")]) * 4 >= state_elems
               for dims in _HLO_SHAPE.findall(result_type)):
            found.append((op.group(1), name.removeprefix("ROOT ")))
    return found


def _entry(hlo_text):
    """The ENTRY computation of a compiled program's text: what runs, each
    fusion one instruction (its body stands above, and would count twice)."""
    return hlo_text[hlo_text.index("\nENTRY "):]


def _cell_chain(case):
    """(state qubits, the fused runs) of a library cell's plan."""
    if case == "sv30-four-runs-k9-k2":     # sv30.block: 60, 23, 6, 2 ops
        n, runs = 30, _planned_runs(_random_circuit(30, depth=2))
        assert [(r.load_swap_k, r.load_swap_hi) for r in runs] \
            == [(0, None), (9, 19), (2, 28), (0, None)]
    elif case == "sv26-three-runs-k7":     # sv26.block: 57, 20 (k=7), 2 ops
        n, runs = 26, _planned_runs(_random_circuit(26, depth=2))
        assert [(r.load_swap_k, r.store_swap_k) for r in runs] \
            == [(0, 0), (7, 7), (0, 0)]
    elif case == "density15-noise-six-runs":   # density15.noise: 2^30
        n, runs = 30, _planned_runs(_noisy_circuit(15))
        # one run at a tile of its own: the pair whose columns straddle 2^19
        assert [(r.tile_bits, r.load_swap_k, r.load_swap_hi)
                for r in runs if r.own_tile] == [(18, 2, 18)]
        # ... and one under the block that grew (PR 42; twelve runs before)
        assert (29, 5, 25) in [(len(r.ops), r.load_swap_k, r.load_swap_hi)
                               for r in runs]
        assert len(runs) <= 7 and all(r.matched for r in runs)
    elif case == "density14-two-runs":     # density14.block: 2^28 amplitudes
        import bench

        n, runs = 28, _planned_runs(bench._density_circuit(
            14, with_krausn=True))
        assert len(runs) == 2
    else:                                  # sv20.block's first two runs
        n, runs = 20, _planned_runs(_random_circuit(20))[:2]
        assert len(runs) == 2 and any(r.load_swap_k for r in runs)
    return n, runs


#: the compiled chain of a cell, once a module (6-25 s a case)
_CHAINS = {}


def _compiled_cell_chain(one_chip, case):
    if case not in _CHAINS:
        n, runs = _cell_chain(case)
        chain = [_fused_kw(n, r.ops, lk=r.load_swap_k, sk=r.store_swap_k,
                           lh=r.load_swap_hi, sh=r.store_swap_hi,
                           sublanes=fusion._run_sublanes(r, PG._DEF_SUBLANES))
                 for r in runs]
        _CHAINS[case] = (n, _compile_chain(one_chip, n, chain))
    return _CHAINS[case]


@pytest.mark.parametrize("case", ["sv30-four-runs-k9-k2", "sv26-three-runs-k7",
                                  "density14-two-runs",
                                  "density15-noise-six-runs"])
def test_a_chain_of_matched_runs_holds_no_state_sized_temporary(one_chip,
                                                                case):
    """Every run of these plans leaves the frame it entered, so its kernel
    writes over its operand (``PG.writes_in_place``): on the donated
    register the whole chain is the argument, aliased to the output, and
    temporaries under an eighth of the state -- where each kernel's output
    was a state-sized buffer of its own (1 GiB for ``sv26``'s shape, 2 GiB
    for ``density14``'s), and 8 GiB beside the 8 GiB of the 30-qubit
    register no chip of 16 GB holds. It also shows that Mosaic and XLA take
    2^31 elements in one array and the two new frame geometries."""
    n, compiled = _compiled_cell_chain(one_chip, case)
    state = 8 << n
    mem = compiled.memory_analysis()
    assert state <= mem.argument_size_in_bytes < state + (1 << 20)
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < state // 8
    assert mem.output_size_in_bytes == state


def test_create_qureg_30q_is_one_buffer(one_chip):
    """``createQureg(30)``'s |0...0> (``ops.init.init_classical``, what
    ``registers._alloc`` calls on one device) is ONE 8 GiB buffer and no
    second: the zeros are written where the result lives."""
    from quest_tpu.ops import init as ops_init

    compiled = jax.jit(
        lambda: ops_init.init_classical(1 << 30, jnp.dtype("float32"), 0),
        out_shardings=one_chip).lower().compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 8 << 30
    assert mem.temp_size_in_bytes < 1 << 20


def test_create_density_qureg_15q_and_its_trace(one_chip):
    """``createDensityQureg(15)``'s |0><0| is ``registers._alloc(env, 30,
    ...)``: ONE 8 GiB buffer, as ``createQureg(30)``'s. ``calcTotalProb`` of
    it (``ops.reduce.total_prob_density``) reads the diagonal where it lies
    and holds nothing of a plane's size -- where the ``reshape(2, dim, dim)``
    and ``jnp.diagonal`` it replaces held two temporaries of 4 GiB, 16 GiB in
    all, and did not compile for the chip."""
    from quest_tpu.ops import init as ops_init
    from quest_tpu.ops import reduce as ops_reduce

    compiled = jax.jit(
        lambda: ops_init.init_classical(1 << 30, jnp.dtype("float32"), 0),
        out_shardings=one_chip).lower().compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 8 << 30
    assert mem.temp_size_in_bytes < 1 << 20
    rho = jax.ShapeDtypeStruct((2, 1 << 30), jnp.float32, sharding=one_chip)
    mem = ops_reduce.total_prob_density.lower(rho, n=15).compile() \
        .memory_analysis()
    assert mem.argument_size_in_bytes == 8 << 30
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("case", ["sv30-four-runs-k9-k2", "sv26-three-runs-k7",
                                  "density14-two-runs", "sv20-two-runs",
                                  "density15-noise-six-runs"])
def test_chained_runs_read_the_register_where_it_lies(one_chip, case):
    """A program shaped like a library cell's -- its fused runs chained at
    the cell's real size, with their load and store swaps, on the donated
    (2, 2^n) parameter -- holds its kernels and NO state-sized copy: the
    view each kernel takes of the register is a bitcast of the T(2,128)
    parameter, and its inverse at the root one too (PR 34; before, a
    relayout copy of the whole state stood on either side)."""
    n, compiled = _compiled_cell_chain(one_chip, case)
    assert _state_sized_traffic(compiled.as_text(), 2 << n) == []


def test_f32_fused_run_26q_plan_pass(one_chip):
    """First pass of the 26q depth-8 plan: manual-DMA kernel, default
    3-slot ring, 100 MiB VMEM limit, 4 MiB tiles."""
    n = 26
    runs = _planned_runs(_random_circuit(n))
    assert len(runs) >= 8
    first = runs[0]
    assert (first.tile_bits, first.load_swap_k, first.store_swap_k) \
        == (PG.local_qubits(n), 0, 0)
    assert PG.ring_depth_default() == 3
    compiled_ops = _fused_kw(n, first.ops)["ops"]
    assert {"butterfly_invreg", "butterfly_rows", "window"} \
        <= {PG.kernel_op_kind(op) for op in compiled_ops}
    _compile_fused(one_chip, n, first.ops)


def test_f32_fused_run_26q_folded_frame_swap(one_chip):
    """A pass of the same plan whose frame swap is folded into the run's
    load AND store DMA (load_swap_k/store_swap_k, 2^k strided row-chunks
    per tile)."""
    n = 26
    swapped = [r for r in _planned_runs(_random_circuit(n))
               if r.load_swap_k and r.store_swap_k]
    assert swapped, "the 26q plan no longer folds a frame swap"
    run = swapped[0]
    # the program folds both into this kernel's DMA on a 26q register
    route = fusion._route(shape_register(n, np.float32), run)
    assert route[:4] == ("local", True, True, None), route
    _compile_fused(one_chip, n, run.ops, lk=run.load_swap_k,
                   sk=run.store_swap_k, lh=run.load_swap_hi,
                   sh=run.store_swap_hi)


def test_f32_per_shard_run_28q_over_4(one_chip):
    """The shard_index form (fused_local_run inside shard_map): one chip's
    2^26-amplitude shard of the 28q plan built for 4 devices, sharded
    qubits resolved from the SMEM shard index, shard-local swap folded."""
    n, ndev = 28, 4
    n_local = n - 2
    runs = _planned_runs(_random_circuit(n), shard_devices=ndev)
    assert runs
    for run in runs:   # every run is per-shard executable
        assert all(q < PG.local_qubits(n_local)
                   for op in run.ops for q in PG.op_dense_targets(op))
    # a run that touches a SHARDED qubit (control / diagonal role)
    plain = next(r for r in runs if any(
        q >= n_local for op in r.ops for q in _op_qubits(op)))
    sharded_role = [op for op in plain.ops
                    if any(q >= n_local for q in _op_qubits(op))][:1]
    assert "butterfly_invreg" in {PG.kernel_op_kind(op) for op in _fused_kw(
        n_local, plain.ops, local_n=n_local)["ops"]}
    _compile_fused(one_chip, n_local, plain.ops, local_n=n_local,
                   must=sharded_role)
    # ... and a run whose frame swap is SHARD-LOCAL, so it folds into the
    # per-shard kernel's BlockSpec index maps (the depth-3 plan has one;
    # swaps reaching sharded bits are collective transposes, not kernels)
    if len(jax.devices()) < ndev:
        pytest.skip("needs the multi-device CPU mesh to ask the router")
    mesh = Mesh(np.array(jax.devices()[:ndev]), (AMP_AXIS,))
    sharded = shape_register(n, np.float32,
                             NamedSharding(mesh, P(None, AMP_AXIS)))
    local_swaps = [r for r in _planned_runs(_random_circuit(n, depth=3),
                                            shard_devices=ndev)
                   if fusion._route(sharded, r)[:4]
                   == ("sharded", True, True, None)]
    assert local_swaps, "no shard-local folded swap in the 28q/4 plan"
    run = local_swaps[0]
    _compile_fused(one_chip, n_local, run.ops[:1], local_n=n_local,
                   lk=run.load_swap_k, lh=run.load_swap_hi,
                   sk=run.store_swap_k, sh=run.store_swap_hi)


def _relabeling_passes(compiled, shard_elems):
    """(whole-shard passes that are not the collective, all-to-alls) of a
    compiled program on one device of the mesh."""
    moved = [op for op, _ in _state_sized_traffic(
        _entry(compiled.as_text()), shard_elems)]
    return (sum(op != "all-to-all" for op in moved),
            moved.count("all-to-all"))


def _between_rows_views(swap, mesh, planes):
    """``swap`` (a relabeling of the sharded (P, 2^n) register) as it stands
    between two per-shard kernels: its operand comes as a kernel hands its
    result over and its result goes as the next kernel takes it, each
    shard the row-interleaved (rows * P, 128) array (``PG._rows_view``)."""
    from jax import shard_map

    rows, amps = P(AMP_AXIS, None), P(None, AMP_AXIS)
    to_planes = shard_map(partial(PG._planes_view, P=planes), mesh=mesh,
                          in_specs=rows, out_specs=amps)
    to_rows = shard_map(PG._rows_view, mesh=mesh, in_specs=amps,
                        out_specs=rows)
    return lambda r: to_rows(swap(to_planes(r)))


@pytest.mark.parametrize("n,lo1,lo2,k,planes", [
    (27, 7, 17, 10, 2), (27, 7, 17, 10, 4), (28, 11, 19, 8, 2),
    (28, 11, 19, 8, 4), (31, 7, 19, 12, 2)],
    ids=["27q-k10-P2", "27q-k10-P4", "28q-k8-P2", "28q-k8-P4",
         "31q-k12-the-cell"])
def test_a_collective_relabeling_is_two_passes_and_one_all_to_all(
        four_chips, n, lo1, lo2, k, planes):
    """The relabeling between two rows-view neighbours over the four
    described chips: per shard with its all-to-all stated
    (``fusion._swap_per_shard``) the chip's compiler makes ``copy``,
    ``all-to-all``, ``copy`` of it. The whole-array ``swap_bit_blocks`` it
    replaces on this route reshapes plane-major and leaves the collective
    to GSPMD, which finds copy, copy, all-to-all, copy, copy: at least FOUR
    passes over the shard, of which two only undo and redo the kernels'
    view (on the chip 12.6-13.06 ms each at 4 GiB a shard, ``PERF.md``
    section 6, PR 40). Temporaries: twice a shard either way. 31 qubits,
    k = 12, lo1 = 7, lo2 = 19 is ``sv31x4.block``'s own relabeling (its
    whole-array form alone does not compile here in minutes and is read at
    27 and 28 qubits; its whole program is the next test); P = 4 is the
    double-float planes. Under a second a compile."""
    sharding = NamedSharding(four_chips, P(None, AMP_AXIS))
    shard = (planes << n) // 4
    rows = jax.ShapeDtypeStruct(
        ((planes << n) >> PG.LANE_BITS, PG._LANES), jnp.float32,
        sharding=NamedSharding(four_chips, P(AMP_AXIS, None)))

    def compiled(swap):
        return jax.jit(_between_rows_views(swap, four_chips, planes),
                       donate_argnums=(0,)).lower(rows).compile()

    per_shard = compiled(fusion._swap_per_shard(four_chips, n, lo1, lo2, k))
    assert _relabeling_passes(per_shard, shard) == (2, 1)
    temp = per_shard.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * 4 * shard
    if n == 31:
        return
    whole = compiled(lambda amps: jax.lax.with_sharding_constraint(
        PG.swap_bit_blocks(amps, n=n, lo1=lo1, lo2=lo2, k=k), sharding))
    passes, collectives = _relabeling_passes(whole, shard)
    assert passes >= 4 and collectives == 1
    assert temp <= whole.memory_analysis().temp_size_in_bytes


def test_sv31x4_program_holds_two_passes_a_relabeling(four_chips,
                                                      monkeypatch):
    """``sv31x4.block``'s whole tape program at its real size for the four
    described chips (31 qubits, 4 GiB a shard; the kernels cut by
    ``_one_of_each`` and lowered for Mosaic, the two collective
    relabelings between them as they really stand): three per-shard
    kernels, two all-to-alls and FOUR whole-shard passes beside them,
    where the whole-array relabeling left eight (PR 40); the donated shard
    aliased to the output, temporaries of two shards (8 GiB), which is
    what the parent's program held. About 10 s."""
    from quest_tpu.circuits import named_program

    n = 31
    circ = Circuit(n)
    _cell_layers().build(circ, num_qubits=n, depth=2, circuit_seed=2026)
    fused = circ.fused(max_qubits=5, pallas=True, dtype=np.float32,
                       shard_devices=4)
    runs = pallas_runs(fused)
    assert [(len(r.ops), r.load_swap_k, r.load_swap_hi, r.store_swap_k)
            for r in runs] == [(62, 0, None, 0), (31, 12, 19, 12),
                               (1, 0, None, 0)]
    fold, run = PG._fold_zone_ops, PG.fused_local_run
    monkeypatch.setattr(PG, "_fold_zone_ops",
                        lambda ops, lq: _one_of_each(fold(ops, lq), lq))
    monkeypatch.setattr(PG, "fused_local_run",
                        lambda amps, **kw: run(amps, **{**kw,
                                                        "interpret": False}))
    amps = jax.ShapeDtypeStruct(
        (2, 1 << n), jnp.float32,
        sharding=NamedSharding(four_chips, P(None, AMP_AXIS)))
    with environment.pallas_mesh(four_chips):
        compiled = jax.jit(named_program(fused.as_fn(), fused, "circuit"),
                           donate_argnums=(0,)).lower(amps).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == len(runs)
    shard = (2 << n) // 4
    assert _relabeling_passes(compiled, shard) == (4, 2)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == mem.output_size_in_bytes \
        == mem.alias_size_in_bytes == 4 * shard
    assert mem.temp_size_in_bytes <= 2 * 4 * shard + (1 << 20)


def _op_qubits(op):
    kind = op[0]
    if kind == "matrix":
        return (op[1],) + tuple(op[2])
    if kind == "parity":
        return tuple(op[1]) + tuple(op[2])
    if kind == "swap":
        return (op[1], op[2]) + tuple(op[3])
    if kind == "diagw":
        return tuple(op[1]) + tuple(op[2])
    return ()


def test_df_run_20q(one_chip, monkeypatch):
    """One double-float kernel (4 f32 planes, DF_SUBLANES tile, a prefix
    of a DF_MAX_OPS chunk) of the 20q plan QUEST_PRECISION=2 runs on the
    chip."""
    n = 20
    # plan at the double-float tile geometry, as the chip does on its own
    # (pallas_df.df_wanted is True on the TPU backend)
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    fz = _random_circuit(n, depth=1).fused(max_qubits=5, pallas=True,
                                           dtype=np.float64)
    runs = pallas_runs(fz)
    assert runs
    lq = PG.local_qubits(n, DF_SUBLANES)
    assert all(q < lq for op in runs[0].ops
               for q in PG.op_dense_targets(op))
    # ... one of them an in-vreg butterfly: the df body exchanges through
    # the same PG._partner, on four planes
    invreg = next(op for op in runs[0].ops
                  if PG.kernel_op_kind(op) == "butterfly_invreg")
    ops = (invreg,) + tuple(op for op in runs[0].ops[:DF_MAX_OPS]
                            if op != invreg)[:_DF_OPS - 1]
    assert len(ops) == _DF_OPS
    _compile_fused(one_chip, n, ops, planes=4, sublanes=DF_SUBLANES)


def test_df26_program_holds_the_register_and_one_set_of_planes(one_chip,
                                                               monkeypatch):
    """``df26.block``'s whole tape program (26 qubits, PRECISION=2) for the
    described chip: twelve df kernels of at most ``DF_MAX_OPS`` ops, one a
    planned run; the donated 1 GiB f64 register aliased to the output; and
    temporaries of ONE set of planes (1 GiB), which every kernel writes in
    place. The planes are carried from kernel to kernel (PR 38): between
    the first and the last kernel nothing but bitcasts stands, ONE fusion
    makes the planes (the split; before it the X64 rewrite's custom calls
    and the fusion that takes the f64 words' high part) and ONE fusion
    after the last kernel reads them (the join). The route is steered here
    as the chip steers it on its own (``jax.default_backend`` reads ``tpu``
    there: the df route, and kernels lowered for Mosaic, not the
    interpreter). About 40 s."""
    from quest_tpu.circuits import named_program

    n = 26
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    circ = Circuit(n)
    _cell_layers().build(circ, num_qubits=n, depth=2, circuit_seed=2026)
    fused = circ.fused(max_qubits=5, pallas=True, dtype=np.float64)
    runs = pallas_runs(fused)
    assert len(runs) == 12 and max(len(r.ops) for r in runs) == DF_MAX_OPS
    amps = jax.ShapeDtypeStruct((2, 1 << n), jnp.float64, sharding=one_chip)
    compiled = jax.jit(named_program(fused.as_fn(), fused, "circuit"),
                       donate_argnums=(0,)).lower(amps).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(runs)
    assert len(set(re.findall(r"qt_fused_dma_df_ops\d+_ls\d+_ss\d+",
                              text))) == 6
    entry = text[text.index("ENTRY"):].splitlines()
    kernels = [i for i, line in enumerate(entry) if "tpu_custom_call" in line]
    assert len(kernels) == len(runs)
    between = re.findall(r"[})] ([a-z-]+)\(",
                         "\n".join(entry[kernels[0]:kernels[-1] + 1]))
    assert set(between) == {"custom-call", "bitcast"}, between
    fusions = [i for i, line in enumerate(entry) if " fusion(" in line]
    assert sum(i < kernels[0] for i in fusions) == 2 \
        and sum(i > kernels[-1] for i in fusions) == 1, fusions
    assert f" = f32[4,{1 << n}]" in entry[fusions[1]]
    state = 16 << n
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == mem.output_size_in_bytes \
        == mem.alias_size_in_bytes == state
    assert state <= mem.temp_size_in_bytes < state + (1 << 20)


def test_density_kraus_run_14q(one_chip):
    """The 14q density circuit (2^28 amplitudes, 2 GiB): the run holding
    the kraus1 (mixDepolarising / mixKrausMap) and krausn
    (mixMultiQubitKrausMap) kernel ops."""
    import bench

    nsv = 28
    runs = _planned_runs(bench._density_circuit(14, with_krausn=True))
    kinds = {op[0] for r in runs for op in r.ops}
    assert {"kraus1", "krausn"} <= kinds, kinds
    run = next(r for r in runs if any(op[0] == "krausn" for op in r.ops))
    _compile_fused(one_chip, nsv, run.ops)


def test_closed_form_depolarising_run_15q(one_chip):
    """The run of ``density15.noise``'s plan at a tile of its own (2^18: the
    frame k=2 @18 that holds the pair whose columns straddle 2^19), with a
    closed-form depolarising op on one target and one on two among its ops
    (``_one_of_each`` keeps one op a kind): the 'depol' body's paired
    exchanges, its masks and the narrowed tile's DMA lower through Mosaic
    at 2^30 elements, in place."""
    runs = _planned_runs(_noisy_circuit(15))
    depol = [op for r in runs for op in r.ops if op[0] == "depol"]
    assert sorted({len(op[1]) for op in depol}) == [1, 2]
    assert not any(op[0].startswith("kraus") for r in runs for op in r.ops)
    run = next(r for r in runs if r.own_tile)
    lq = run.tile_bits
    must = tuple(next(op for op in depol if len(op[1]) == t
                      and all(q < lq for q in PG.op_dense_targets(op)))
                 for t in (1, 2))
    compiled = _compile_fused(
        one_chip, 30, run.ops, must=must, lk=run.load_swap_k,
        sk=run.store_swap_k, lh=run.load_swap_hi, sh=run.store_swap_hi,
        sublanes=fusion._run_sublanes(run, PG._DEF_SUBLANES))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 8 << 30
    assert mem.temp_size_in_bytes < 1 << 30


def test_grown_frame_run_15q_whole(one_chip):
    """The run of ``density15.noise``'s plan under the block that grew
    (``k=5 @25``: bits 25-29 in, 14-18 out), WHOLE: its 29 ops, not one of
    each kind -- eight closed-form channels on one target and three on two
    among them, behind a load and a store relabeling of 2^5 row chunks --
    lower through Mosaic at 2^30 elements within the kernel's VMEM, in
    place (PR 42; the seven kernels it replaces held one to ten ops).
    About 20 s."""
    run = next(r for r in _planned_runs(_noisy_circuit(15))
               if (r.load_swap_k, r.load_swap_hi) == (5, 25))
    assert len(run.ops) == 29 and run.matched and not run.own_tile
    depol = [len(op[1]) for op in run.ops if op[0] == "depol"]
    assert (depol.count(1), depol.count(2)) == (8, 3)
    compiled = _compile_fused(one_chip, 30, run.ops, whole=True, lk=5, sk=5,
                              lh=25, sh=25)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 8 << 30
    assert mem.temp_size_in_bytes < 1 << 20


def _dot_generals(jaxpr, state, found):
    """``dot_general`` equations of ``jaxpr`` and of every jaxpr nested in
    it, told apart by what they touch: an ``application`` writes a
    register (a result of ``state`` elements or more), a ``contraction``
    reads two and writes something small."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            def size(v):
                return int(np.prod(v.aval.shape))
            if size(eqn.outvars[0]) >= state:
                found["application"] += 1
            elif sum(size(v) >= state for v in eqn.invars) == 2:
                found["contraction"] += 1
        for param in eqn.params.values():
            for inner in (param if isinstance(param, (list, tuple))
                          else [param]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _dot_generals(inner, state, found)
    return found


def _grad_batch_lowered(one_chip, monkeypatch):
    """``ansatz20.grad-closed8``'s batch program
    (``jit_qt_engine_vmap_sv_n20_g202_b8``: the 20-qubit depth-4 ansatz,
    six Pauli strings, eight lanes under ``vmap``) lowered for the
    described chip: ``(lowered, reduce, n, lanes)``. The lanes are steered
    to ``vmap`` as the chip steers them (``jax.default_backend`` reads
    ``tpu`` there; the CPU runs them as a ``lax.map`` scan)."""
    import sys

    import quest_tpu as qt
    from quest_tpu.engine import Engine, P as Param
    from quest_tpu.parallel import scheduler as _dist

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from circuits import serving_ansatz

    n, depth, lanes = 20, 4, 8
    rng = np.random.RandomState(20)
    codes = rng.randint(0, 4, size=(6, n)).astype(np.int32)
    coeffs = rng.normal(size=6)
    circ = Circuit(n)
    serving_ansatz.build(circ, angle=Param, num_qubits=n, depth=depth)
    env = qt.createQuESTEnv(jax.devices()[:1])
    engine = Engine(circ, env, precision_code=1, hamiltonian=(codes, coeffs),
                    max_batch=lanes, max_delay_ms=0.5)
    try:
        grad = engine.grad_engine()
        reduce = grad._finalize
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        batch = grad._execB()
        monkeypatch.undo()
        assert batch.__name__ == "qt_engine_vmap_sv_n20_g202_b8"
        jitted = batch.__kwdefaults__["_inner"]
        args = [jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                     sharding=one_chip)]
        args += [jax.ShapeDtypeStruct((lanes, len(cols)), jnp.float32,
                                      sharding=one_chip)
                 for _, cols in grad._packs]
        with _dist.explicit_mesh(None), environment.pallas_mesh(None):
            lowered = jitted.lower(*args)
    finally:
        engine.close()
    return lowered, reduce, n, lanes


def test_grad_batch_program_walks_blocks_and_fits(one_chip, monkeypatch):
    """What ONE lane's reduce of ``ansatz20.grad-closed8``'s batch program
    holds, by its jaxpr: the backward half walks the 30 blocks of the
    tape's dense plan (PR 45), 26 of them dense windows undone by one GEMM
    on ``phi`` and one on ``lambda`` (the four diagonals are elementwise
    passes), and harvests all 160 derivatives from 12 window contractions;
    the costate's 68 one-qubit Pauli applications stand as they did (the
    gate walk held 384 applications there and no contraction). And what
    the program lowered for the described chip carries: its name, and ONE
    ``(8, 321)`` array handed back a launch (PR 46). No backend compile:
    whether it FITS is :func:`test_grad_batch_program_fits_the_chip`."""
    from quest_tpu.gradients import apply_hamiltonian

    lowered, reduce, n, lanes = _grad_batch_lowered(one_chip, monkeypatch)
    state = 2 << n
    amps = jax.ShapeDtypeStruct((2, 1 << n), jnp.float32)
    values = tuple(jax.ShapeDtypeStruct((), jnp.float32)
                   for _ in range(reduce.num_slots))

    def count(fn, *args):
        return _dot_generals(jax.make_jaxpr(fn)(*args).jaxpr, state,
                             {"application": 0, "contraction": 0})

    h_codes, h_coeffs = reduce.hamiltonian
    costate = count(lambda a: apply_hamiltonian(
        a, codes=h_codes, coeffs=h_coeffs, num_qubits=n), amps)
    assert costate == {"application": 68, "contraction": 0}
    assert count(reduce, amps, values) == {
        "application": 68 + 2 * 26, "contraction": 12}
    # what the program hands back a launch (PR 46): ONE array, a row a lane
    # of the value, 160 slot derivatives and 160 named ones; the lanes' 321
    # numbers as outputs of their own were 2,568 arrays
    assert [out.shape for out in jax.tree_util.tree_leaves(
        lowered.out_info)] == [(lanes, 1 + 2 * reduce.num_slots)]


@pytest.mark.slow
def test_grad_batch_program_fits_the_chip(one_chip, monkeypatch):
    """The chip compiler's ``memory_analysis`` of the whole batch program:
    the gate walk read 10.6 GB of temporaries and 3.24 GB of generated
    code (PERF.md section 7, PR 44), and the ceilings here stand under 60%
    of both. Marked ``slow``: the backend compile of this ONE program is
    about four minutes on a sandbox core, a quarter of tier-1's limit
    (ISSUE 47), and the cell itself, which the driver runs on the chip on
    every PR, is what notices a batch program that stops fitting."""
    lowered = _grad_batch_lowered(one_chip, monkeypatch)[0]
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 1.6e9, mem.temp_size_in_bytes
    assert mem.generated_code_size_in_bytes < 1.6e9, \
        mem.generated_code_size_in_bytes
