"""General gate application: the single kernel family every unitary reduces to.

The reference funnels all dense gates into
``statevec_multiControlledMultiQubitUnitary`` (gather 2^t amps / dense matvec /
scatter per task, ``QuEST_cpu.c:1840-1952``; per-gate MPI choreography
``QuEST_cpu_distributed.c:1526-1568``). The TPU-native formulation: view the
planar (2, 2^n) state as a grouped tensor (:mod:`.layout`), transpose the
touched 2-sized axes to the front, and hit them with 4 small real matmuls
(complex matmul over the planes) -- XLA tiles them onto the MXU and, when the
array is sharded over the top qubits, inserts the all-to-all /
collective-permute traffic that the reference hand-writes.

Matrix index convention matches the reference (multiQubitUnitary doc): the
row index r of the 2^t x 2^t matrix is ``sum_k bit(targets[k]) << k`` --
targets[0] is the least-significant bit of the matrix index. Matrices arrive
planar: shape (2, 2^t, 2^t).

All functions are pure and jitted with static qubit tuples: one XLA program
per (n, targets, controls) signature, reused across angles/matrices.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .layout import amps_jit, grouped_axes, inverse_permutation
from .spy import records


def _plan(n, targets, controls):
    """Common transpose plan: (shape, perm, inv_perm) with the leading planar
    axis pinned at 0, controls then targets(MSB-first) next."""
    shape, axis_of = grouped_axes(n, tuple(targets) + tuple(controls))
    ctrl_axes = [axis_of[c] + 1 for c in controls]
    targ_axes = [axis_of[q] + 1 for q in reversed(targets)]
    rest = [a for a in range(1, len(shape) + 1) if a not in ctrl_axes and a not in targ_axes]
    perm = tuple([0] + ctrl_axes + targ_axes + rest)
    return (2,) + shape, perm, inverse_permutation(perm)


#: windows whose low edge is below this get kron-expanded down to qubit 0 so
#: the GEMM's K dimension is at least 2^_MIN_MINOR (=the 128-lane width);
#: keeps every buffer's trailing dim >= 128 and avoids TPU tile padding.
_MIN_MINOR = 7

#: widest window the serving Engine's dense plan fuses
#: (Engine._plan_program). The chip's pick: ansatz20.serve-closed16 on one
#: v5e, final tree, two seeds each (PR 27, call 5), read 159.3 / 160.6
#: requests/s, a p50 of 100.4 / 99.5 ms and 32.2 ms of device time a batch
#: at 5; 106.6 / 106.4, 149.9 / 150.3 and 57.3 at 6; 219.9 / 217.9, 72.8 /
#: 73.3 and 20.4 at 7 (the first cut, call 1, one seed: 87.4, 68.0, 105.1
#: requests/s). At 7 a layer of one-qubit gates falls into windows aligned
#: to the lane boundary ([0-6], [7-13], ...); at 5 and 6 one window of
#: every layer straddles it ([5-9], [6-10]) and pays a K = 1024 / 2048 GEMM.
DENSE_WINDOW_QUBITS = 7

#: a window that starts below _MIN_MINOR is expanded down to qubit 0, so
#: its GEMM's K is 2^(top+1) however few qubits it spans: the planner
#: (planner.plan) opens no such window whose top reaches this qubit, which
#: holds K to the [128, 2048] range _apply_matrix_window is written for
MAX_LOW_WINDOW_TOP = 11


def _mxu_precision(dtype):
    """Always HIGHEST: XLA:TPU's default silently drops matmul inputs to
    bf16 -- catastrophic for amplitude evolution (observed 3e-3 norm drift in
    an 8-amp state). HIGH (3-pass bf16) was measured no faster here and
    drifted a 26q depth-8 circuit's norm to 0.9964 (vs 1.000002 at HIGHEST);
    the dtype hook stays so a future backend can relax it deliberately."""
    del dtype
    return jax.lax.Precision.HIGHEST


def _window_of(targets):
    """(lo, hi) if ``targets`` is exactly the ascending run lo..hi, else None."""
    t = len(targets)
    lo = targets[0]
    if targets == tuple(range(lo, lo + t)):
        return lo, lo + t - 1
    return None


def _apply_matrix_window(amps, mr, mi, n, lo, hi):
    """Layout-clean dense apply for a contiguous target window [lo, hi].

    The general grouped-transpose path materialises high-rank tensors whose
    trailing dims are 2-sized; the TPU's (8, 128) tile padding then inflates
    them up to 64x (observed: a 512 MB state demanding a 32 GB allocation).
    A contiguous window never needs a transpose:

    - lo >= _MIN_MINOR: view (2, A, 2^t, 2^lo) and contract the 2^t axis
      with M -- trailing dim 2^lo >= 128, no padding, MXU GEMM.
    - lo < _MIN_MINOR: expand M to G = I (x) M (x) I over the low
      w = max(hi+1, _MIN_MINOR) qubits and right-multiply the (2, R, 2^w)
      view -- K in [128, 2048], the MXU sweet spot.
    """
    mm = partial(jnp.einsum, precision=_mxu_precision(amps.dtype))

    def cplx_block(gr, gi):
        # the complex product as ONE real contraction: out[p] = sum_q G4[p,q] x[q]
        # with G4 = [[gr, -gi], [gi, gr]] -- reads the state once instead of
        # four times (one dot_general, planes contracted alongside K).
        return jnp.stack([jnp.stack([gr, -gi]), jnp.stack([gi, gr])])

    if lo >= _MIN_MINOR:
        dim = 1 << (hi - lo + 1)
        x = amps.reshape(2, -1, dim, 1 << lo)
        g4 = cplx_block(mr, mi)
        out = mm("pqij,qajb->paib", g4, x)
        return out.reshape(2, -1)

    w = min(max(hi + 1, _MIN_MINOR), n)
    eye_hi = jnp.eye(1 << (w - 1 - hi), dtype=mr.dtype)
    eye_lo = jnp.eye(1 << lo, dtype=mr.dtype)
    gr = jnp.kron(eye_hi, jnp.kron(mr, eye_lo))
    gi = jnp.kron(eye_hi, jnp.kron(mi, eye_lo))
    g4 = cplx_block(gr, gi)
    x = amps.reshape(2, -1, 1 << w)
    out = mm("pqij,qaj->pai", g4, x)
    return out.reshape(2, -1)


@records
@amps_jit(static_argnames=("n", "targets", "controls", "control_states", "conj"),
          donate_argnums=(0,))
def apply_matrix(amps, matrix, *, n: int, targets: tuple[int, ...],
                 controls: tuple[int, ...] = (), control_states: tuple[int, ...] = (),
                 conj: bool = False):
    """amps' = (ctrl-gated) M applied to ``targets`` of the n-qubit state.

    ``matrix`` is planar (2, 2^t, 2^t) and may be non-unitary (the apply*
    operator family reuses this). ``control_states`` optionally gives the
    required value of each control (default all-1, as
    multiStateControlledUnitary, QuEST.h:4448). ``conj=True`` applies the
    elementwise conjugate (density-matrix shadow op, QuEST.c:184-193).
    """
    t = len(targets)
    dim = 1 << t
    states = control_states if control_states else (1,) * len(controls)

    mr, mi = matrix[0], matrix[1]
    if conj:
        mi = -mi

    if not controls:
        win = _window_of(targets)
        if win is not None:
            return _apply_matrix_window(amps, mr, mi, n, *win)

    shape, perm, inv = _plan(n, targets, controls)
    tensor = amps.reshape(shape).transpose(perm)

    # see _mxu_precision: never let XLA silently drop matmul inputs to bf16
    mm = partial(jnp.matmul, precision=_mxu_precision(amps.dtype))

    def matvec(sub):
        # sub: (2, 2, 2, ..., rest) with t leading 2-axes after the plane
        flat = sub.reshape(2, dim, -1)
        rr = mm(mr, flat[0]) - mm(mi, flat[1])
        ii = mm(mr, flat[1]) + mm(mi, flat[0])
        return jnp.stack([rr, ii]).reshape(sub.shape)

    if controls:
        idx = (slice(None),) + tuple(states)
        sub = tensor[idx]
        tensor = tensor.at[idx].set(matvec(sub))
    else:
        tensor = matvec(tensor)

    return tensor.transpose(inv).reshape(2, -1)


#: X/swap supports spanning at most this many contiguous qubits are applied
#: as a host-built permutation matrix through the window GEMM (layout-clean);
#: wider spans fall back to the grouped view, whose tile padding makes it
#: unusable on large states but fine on small ones.
_PERM_WINDOW_MAX = 8


def _window_perm_matrix(span_lo, span_hi, flips, cbits, states, np_dtype):
    """Permutation matrix over the window [span_lo, span_hi]: XOR ``flips``
    where every control bit matches its required state; identity elsewhere.
    All-static, built host-side at trace time."""
    import numpy as np
    k = span_hi - span_lo + 1
    dim = 1 << k
    mr = np.zeros((dim, dim), dtype=np_dtype)
    fl = 0
    for q in flips:
        fl |= 1 << (q - span_lo)
    for s in range(dim):
        ok = all(((s >> (c - span_lo)) & 1) == st for c, st in zip(cbits, states))
        mr[s ^ fl if ok else s, s] = 1
    return mr


@amps_jit(static_argnames=("n", "targets", "controls", "control_states"),
          donate_argnums=(0,))
def apply_x_class(amps, *, n: int, targets: tuple[int, ...],
                  controls: tuple[int, ...] = (), control_states: tuple[int, ...] = ()):
    """Multi-controlled multi-qubit NOT: an amplitude permutation.

    The reference's pauliX/controlledNot/multiControlledMultiQubitNot kernels
    (``QuEST_cpu.c``, dispatch ``QuEST_cpu_distributed.c:1109-1152``) are
    strided-copy loops. Here, compact supports become a control-folded
    permutation matrix through the layout-clean window GEMM; wide supports
    take the grouped flip (fine at small n, sharded axes become collective
    permutes).
    """
    states = control_states if control_states else (1,) * len(controls)
    support = tuple(targets) + tuple(controls)
    lo, hi = min(support), max(support)
    if hi - lo + 1 <= _PERM_WINDOW_MAX:
        import numpy as np
        mr = _window_perm_matrix(lo, hi, targets, controls, states,
                                 np.dtype(amps.dtype))
        m = jnp.stack([jnp.asarray(mr), jnp.zeros_like(jnp.asarray(mr))])
        return _apply_matrix_window(amps, m[0], m[1], n, lo, hi)
    shape, perm, inv = _plan(n, targets, controls)
    tensor = amps.reshape(shape).transpose(perm)
    nc = len(controls)
    flip_axes = list(range(1 + nc, 1 + nc + len(targets)))

    if controls:
        idx = (slice(None),) + tuple(states)
        sub = tensor[idx]
        sub = jnp.flip(sub, axis=[a - nc for a in flip_axes])
        tensor = tensor.at[idx].set(sub)
    else:
        tensor = jnp.flip(tensor, axis=flip_axes)

    return tensor.transpose(inv).reshape(2, -1)


@records
@amps_jit(static_argnames=("n", "qb1", "qb2", "controls"), donate_argnums=(0,))
def apply_swap(amps, *, n: int, qb1: int, qb2: int, controls: tuple[int, ...] = ()):
    """SWAP as an axis transposition (reference: statevec_swapQubitAmps,
    ``QuEST_cpu.c:3850-3931``; distributed odd-parity pair exchange
    ``QuEST_cpu_distributed.c:1424-1459``). On a sharded axis this *is* the
    all-to-all the reference hand-codes -- and it is also the primitive the
    distributed scheduler uses to localise far targets."""
    support = (qb1, qb2) + tuple(controls)
    lo, hi = min(support), max(support)
    if hi - lo + 1 <= _PERM_WINDOW_MAX:
        import numpy as np
        k = hi - lo + 1
        dim = 1 << k
        mr = np.zeros((dim, dim), dtype=np.dtype(amps.dtype))
        b1, b2 = qb1 - lo, qb2 - lo
        for s in range(dim):
            ok = all(((s >> (c - lo)) & 1) == 1 for c in controls)
            if ok:
                v1, v2 = (s >> b1) & 1, (s >> b2) & 1
                s2 = s & ~(1 << b1) & ~(1 << b2) | (v2 << b1) | (v1 << b2)
            else:
                s2 = s
            mr[s2, s] = 1
        m = jnp.asarray(mr)
        return _apply_matrix_window(amps, m, jnp.zeros_like(m), n, lo, hi)

    shape, perm, inv = _plan(n, (qb1, qb2), controls)
    tensor = amps.reshape(shape).transpose(perm)
    nc = len(controls)
    a1, a2 = 1 + nc, 2 + nc  # the two target axes after the plan's transpose

    if controls:
        idx = (slice(None),) + (1,) * nc
        sub = tensor[idx]
        sp = list(range(sub.ndim))
        sp[a1 - nc], sp[a2 - nc] = sp[a2 - nc], sp[a1 - nc]
        tensor = tensor.at[idx].set(sub.transpose(sp))
    else:
        sp = list(range(tensor.ndim))
        sp[a1], sp[a2] = sp[a2], sp[a1]
        tensor = tensor.transpose(sp)

    return tensor.transpose(inv).reshape(2, -1)
