"""The benchmark's plain reference: numpy complex128, gate by gate.

Nothing here imports the program. A circuit builder (``circuits/*.py``)
records onto a :class:`Tape`; :func:`run_statevector` replays the tape on a
full 2^n vector (in place, over a few host threads, because 2^26 complex128
amplitudes are 1 GiB a pass), and :func:`run_density_blocks` replays it on
sampled blocks of a density matrix that are closed under the circuit.

``lower`` is the hook of the CONTROL (``control.py``): a function that rounds
an array to the next precision below the configuration's (bfloat16 for f32).
With it every gate matrix and every written amplitude is rounded, which is
what the same replay would give in that arithmetic. The benchmark's own runs
never pass it.

Conventions are QuEST's: qubit 0 is the least significant bit of a basis
index; a multi-target matrix indexes its first target as its least
significant bit; a density matrix element rho[row, col] lives at flat index
``col * 2^n + row``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


class Tape:
    """Records ``tape.<gate>(*args)`` calls as ``(name, args)``."""

    def __init__(self):
        self.ops = []

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def record(*args):
            self.ops.append((name, args))

        return record


_I2 = np.eye(2, dtype=complex)
_X = np.array([[0, 1], [1, 0]], dtype=complex)
_Y = np.array([[0, -1j], [1j, 0]])
_Z = np.diag([1.0 + 0j, -1.0])
_H = (_X + _Z) / np.sqrt(2)


def _unitary(name, args):
    """(target, 2x2 matrix, controls) of a gate, or None for a channel."""
    if name == "hadamard":
        return args[0], _H, ()
    if name == "tGate":
        return args[0], np.diag([1, np.exp(0.25j * np.pi)]), ()
    if name == "rotateZ":
        q, th = args
        return q, np.diag([np.exp(-0.5j * th), np.exp(0.5j * th)]), ()
    if name == "rotateX":
        q, th = args
        c, s = np.cos(th / 2), np.sin(th / 2)
        return q, np.array([[c, -1j * s], [-1j * s, c]]), ()
    if name == "controlledNot":
        return args[1], _X, (args[0],)
    if name == "controlledPhaseFlip":
        return args[1], _Z, (args[0],)
    return None


def _kraus(name, args):
    """(targets, Kraus operators) of a channel; targets[0] is the least
    significant bit of the operators' index."""
    if name == "mixDepolarising":
        q, p = args
        return (q,), [np.sqrt(1 - p) * _I2] + [np.sqrt(p / 3) * m
                                                 for m in (_X, _Y, _Z)]
    if name == "mixKrausMap":
        return (args[0],), [np.asarray(k, dtype=complex) for k in args[1]]
    if name == "mixTwoQubitDephasing":
        q1, q2, p = args
        zi, iz = np.kron(_I2, _Z), np.kron(_Z, _I2)   # Z on q1, Z on q2
        return (q1, q2), [np.sqrt(1 - p) * np.eye(4), np.sqrt(p / 3) * zi,
                          np.sqrt(p / 3) * iz, np.sqrt(p / 3) * (zi @ iz)]
    if name == "mixMultiQubitKrausMap":
        return tuple(args[0]), [np.asarray(k, dtype=complex) for k in args[1]]
    raise ValueError(f"reference: unknown tape entry {name!r}")


def support(ops) -> list:
    """Sorted qubits the tape touches."""
    qs = set()
    for name, args in ops:
        u = _unitary(name, args)
        if u is not None:
            qs.update((u[0],) + tuple(u[2]))
        else:
            qs.update(_kraus(name, args)[0])
    return sorted(qs)


# ---------------------------------------------------------------------------
# state-vector replay
# ---------------------------------------------------------------------------

def host_threads() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 1))


def _chunks(shape, parts):
    """Index tuples cutting the longest axis of ``shape`` into ``parts``."""
    ax = int(np.argmax(shape))
    size = shape[ax]
    parts = max(1, min(parts, size))
    step = -(-size // parts)
    for lo in range(0, size, step):
        yield (slice(None),) * ax + (slice(lo, lo + step),)


def _apply_1q(psi, t, m, controls, pool, parts, lower):
    """One-target gate with at most one control, in place on ``psi``."""
    if not controls:
        v = psi.reshape(-1, 2, 1 << t)
        a, b = v[:, 0], v[:, 1]
    else:
        (c,) = controls
        lo, hi = sorted((c, t))
        v = psi.reshape(-1, 2, 1 << (hi - lo - 1), 2, 1 << lo)
        a, b = (v[:, 1, :, 0], v[:, 1, :, 1]) if c == hi else \
            (v[:, 0, :, 1], v[:, 1, :, 1])
    if lower is not None:
        m = lower(m)
    diag = m[0, 1] == 0 and m[1, 0] == 0

    def work(sl):
        x, y = a[sl], b[sl]
        if diag:
            nx = x if m[0, 0] == 1 else m[0, 0] * x
            ny = y if m[1, 1] == 1 else m[1, 1] * y
        else:
            nx = m[0, 0] * x + m[0, 1] * y
            ny = m[1, 0] * x + m[1, 1] * y
        if lower is not None:
            nx, ny = lower(nx), lower(ny)
        if nx is not x:
            a[sl] = nx
        if ny is not y:
            b[sl] = ny

    slices = list(_chunks(a.shape, parts))
    if pool is None or len(slices) == 1:
        for sl in slices:
            work(sl)
    else:
        list(pool.map(work, slices))


def run_statevector(psi0, ops, lower=None, threads: int | None = None):
    """The tape applied to a copy of ``psi0`` (complex128, length 2^n)."""
    psi = np.array(psi0, dtype=np.complex128)
    if lower is not None:
        psi = lower(psi)
    threads = host_threads() if threads is None else threads
    # below 2^22 amplitudes the slices are too small to pay for the hand-over
    big = psi.size >= 1 << 22 and threads > 1
    pool = ThreadPoolExecutor(threads) if big else None
    try:
        for name, args in ops:
            u = _unitary(name, args)
            if u is None:
                raise ValueError(f"reference: {name!r} is not a state-vector gate")
            _apply_1q(psi, u[0], u[1], u[2], pool, 4 * threads, lower)
    finally:
        if pool is not None:
            pool.shutdown()
    return psi


# ---------------------------------------------------------------------------
# density blocks
# ---------------------------------------------------------------------------

def _embed(m, targets, nloc):
    """``m`` on local qubits ``targets`` (first = least significant) as a
    2^nloc square matrix."""
    d, k = 1 << nloc, len(targets)
    mask = sum(1 << t for t in targets)
    full = np.zeros((d, d), dtype=complex)

    def deposit(sub):
        return sum(((sub >> j) & 1) << t for j, t in enumerate(targets))

    outs = [deposit(s) for s in range(1 << k)]
    for i in range(d):
        sub_i = sum(((i >> t) & 1) << j for j, t in enumerate(targets))
        base = i & ~mask
        for sub_o, dep in enumerate(outs):
            full[base | dep, i] = m[sub_o, sub_i]
    return full


def _controlled(m):
    """2-qubit matrix of ``m`` on bit 0 controlled by bit 1."""
    out = np.eye(4, dtype=complex)
    out[2:, 2:] = m
    return out


def block_indices(n, active, spect_rows, spect_cols):
    """Flat indices (P, d, d) into a 2n-qubit density register of the blocks
    whose spectator row/column bits are ``spect_rows[p]``/``spect_cols[p]``:
    element [p, a, b] is rho[(a, r_p), (b, c_p)], ``a``/``b`` running over the
    ``active`` qubits."""
    spectators = [q for q in range(n) if q not in active]

    def spread(values, qubits):
        values = np.asarray(values, dtype=np.int64)
        return sum(((values >> j) & 1) << q for j, q in enumerate(qubits))

    act = spread(np.arange(1 << len(active)), active)
    rows = spread(spect_rows, spectators)[:, None] | act[None, :]     # (P, d)
    cols = spread(spect_cols, spectators)[:, None] | act[None, :]
    return (cols[:, None, :] << n) | rows[:, :, None]


def run_density_blocks(psi0, n, ops, spect_rows, spect_cols, lower=None):
    """Blocks (P, d, d) of ``C(|psi0><psi0|)``, ``C`` the tape's channel:
    the tape acts on its support only, so a block of fixed spectator bits is
    closed under it."""
    active = support(ops)
    if len(active) > 8:
        raise ValueError(f"reference: the tape touches {len(active)} qubits; "
                         "block sampling needs a support of at most 8")
    loc = {q: j for j, q in enumerate(active)}
    nloc = len(active)
    psi0 = np.asarray(psi0, dtype=np.complex128)
    idx = block_indices(n, active, spect_rows, spect_cols)
    rows, cols = idx[:, :, 0] & ((1 << n) - 1), idx[:, 0, :] >> n
    blocks = psi0[rows][:, :, None] * np.conj(psi0[cols])[:, None, :]
    if lower is not None:
        blocks = lower(blocks)
    for name, args in ops:
        u = _unitary(name, args)
        if u is not None:
            t, m, ctl = u
            if ctl:
                ks = [_embed(_controlled(m), (loc[t], loc[ctl[0]]), nloc)]
            else:
                ks = [_embed(m, (loc[t],), nloc)]
        else:
            targets, mats = _kraus(name, args)
            ks = [_embed(k, tuple(loc[q] for q in targets), nloc) for k in mats]
        if lower is not None:
            ks = [lower(k) for k in ks]
        new = sum(k @ blocks @ k.conj().T for k in ks)
        blocks = lower(new) if lower is not None else new
    return blocks


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def errors(got_re, got_im, want) -> tuple:
    """(max |got - want| / max |want|, ||got - want|| / ||want||), taken in
    slices so a 2^26 vector needs no second copy."""
    got_re, got_im = np.ravel(got_re), np.ravel(got_im)
    want = np.ravel(want)
    worst = scale = num = den = 0.0
    step = 1 << 22
    for lo in range(0, want.size, step):
        w = want[lo:lo + step]
        dr = got_re[lo:lo + step].astype(np.float64) - w.real
        di = got_im[lo:lo + step].astype(np.float64) - w.imag
        d2 = dr * dr + di * di
        w2 = w.real * w.real + w.imag * w.imag
        worst = max(worst, float(d2.max()))
        scale = max(scale, float(w2.max()))
        num += float(d2.sum())
        den += float(w2.sum())
    return float(np.sqrt(worst / scale)), float(np.sqrt(num / den))
