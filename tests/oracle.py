"""Dense linear-algebra oracle for correctness tests.

The reference proves its kernels against "algorithmically distinct,
unoptimised" dense algebra (tests/utilities.hpp:1-12: QVector/QMatrix with
Kronecker-product operator construction, applied to replicated full states).
This module is the numpy equivalent: states are complex vectors / matrices,
operators are built entry-by-entry from explicit bit arithmetic
(tests/utilities.hpp:348 getFullOperatorMatrix), and channels are applied as
sum_k K rho K^dagger. Nothing here shares code with quest_tpu.ops.
"""

from __future__ import annotations

import numpy as np


def full_operator(n: int, targets, matrix, controls=(), control_states=None) -> np.ndarray:
    """Dense 2^n x 2^n operator applying ``matrix`` to ``targets`` when all
    ``controls`` match ``control_states`` (default all-1), identity elsewhere.
    targets[0] is the least-significant bit of the matrix index."""
    dim = 1 << n
    t = len(targets)
    m = np.asarray(matrix, dtype=np.complex128)
    states = control_states if control_states is not None else [1] * len(controls)
    F = np.zeros((dim, dim), dtype=np.complex128)
    for i in range(dim):
        if not all(((i >> c) & 1) == s for c, s in zip(controls, states)):
            F[i, i] = 1.0
            continue
        r_in = 0
        for k, q in enumerate(targets):
            r_in |= ((i >> q) & 1) << k
        base = i
        for q in targets:
            base &= ~(1 << q)
        for r_out in range(1 << t):
            j = base
            for k, q in enumerate(targets):
                if (r_out >> k) & 1:
                    j |= 1 << q
            F[j, i] = m[r_out, r_in]
    return F


def apply_to_statevec(state: np.ndarray, n, targets, matrix, controls=(),
                      control_states=None) -> np.ndarray:
    return full_operator(n, targets, matrix, controls, control_states) @ state


def apply_to_statevec_indexed(state: np.ndarray, n, targets, matrix,
                              controls=(), control_states=None) -> np.ndarray:
    """``apply_to_statevec`` without the 4^n operator, for registers too
    wide to build it: the same explicit bit arithmetic, carried out on the
    vector of all 2^n indices at once (each output amplitude gathers the
    2^t inputs that differ from it on ``targets`` only)."""
    m = np.asarray(matrix, dtype=np.complex128)
    idx = np.arange(1 << n)
    states = control_states if control_states is not None else [1] * len(controls)
    active = np.ones(1 << n, dtype=bool)
    for c, s in zip(controls, states):
        active &= ((idx >> c) & 1) == s
    r_out = np.zeros(1 << n, dtype=np.int64)
    base = idx.copy()
    for k, q in enumerate(targets):
        r_out |= ((idx >> q) & 1) << k
        base &= ~(1 << q)
    out = np.zeros(1 << n, dtype=np.complex128)
    for r_in in range(1 << len(targets)):
        j = base.copy()
        for k, q in enumerate(targets):
            if (r_in >> k) & 1:
                j |= 1 << q
        out += m[r_out, r_in] * state[j]
    return np.where(active, out, state)


def apply_to_density(rho: np.ndarray, n, targets, matrix, controls=(),
                     control_states=None) -> np.ndarray:
    F = full_operator(n, targets, matrix, controls, control_states)
    return F @ rho @ F.conj().T


def apply_kraus_to_density(rho: np.ndarray, n, targets, kraus_ops) -> np.ndarray:
    out = np.zeros_like(rho)
    for k in kraus_ops:
        F = full_operator(n, targets, k)
        out += F @ rho @ F.conj().T
    return out


def debug_statevec(num_amps: int) -> np.ndarray:
    """amp_i = (2i + (2i+1) j) / 10, as initDebugState."""
    i = np.arange(num_amps)
    return (2 * i + 1j * (2 * i + 1)) / 10.0


def random_statevec(n: int, rng: np.random.RandomState) -> np.ndarray:
    v = rng.randn(1 << n) + 1j * rng.randn(1 << n)
    return v / np.linalg.norm(v)


def random_density(n: int, rng: np.random.RandomState) -> np.ndarray:
    """Random mixed state: convex sum of a few random pure states."""
    dim = 1 << n
    rho = np.zeros((dim, dim), dtype=np.complex128)
    ws = rng.rand(3)
    ws /= ws.sum()
    for w in ws:
        v = random_statevec(n, rng)
        rho += w * np.outer(v, v.conj())
    return rho


def random_unitary(t: int, rng: np.random.RandomState) -> np.ndarray:
    """Haar-ish random unitary via QR of a Ginibre matrix."""
    dim = 1 << t
    g = rng.randn(dim, dim) + 1j * rng.randn(dim, dim)
    q, r = np.linalg.qr(g)
    return q * (np.diagonal(r) / np.abs(np.diagonal(r)))


def random_kraus(t: int, num_ops: int, rng: np.random.RandomState):
    """Random CPTP Kraus set: random Ginibre operators whitened by the inverse
    square root of their closure sum (so sum K^dag K = I exactly)."""
    dim = 1 << t
    raw = [rng.randn(dim, dim) + 1j * rng.randn(dim, dim) for _ in range(num_ops)]
    closure = sum(k.conj().T @ k for k in raw)
    w, v = np.linalg.eigh(closure)
    inv_sqrt = v @ np.diag(1.0 / np.sqrt(w)) @ v.conj().T
    ops = [k @ inv_sqrt for k in raw]
    acc = sum(op.conj().T @ op for op in ops)
    assert np.allclose(acc, np.eye(dim), atol=1e-10)
    return ops


def pauli_matrix(code: int) -> np.ndarray:
    return {
        0: np.eye(2, dtype=np.complex128),
        1: np.array([[0, 1], [1, 0]], dtype=np.complex128),
        2: np.array([[0, -1j], [1j, 0]], dtype=np.complex128),
        3: np.array([[1, 0], [0, -1]], dtype=np.complex128),
    }[int(code)]


def pauli_product_matrix(n: int, targets, codes) -> np.ndarray:
    m = np.eye(1 << n, dtype=np.complex128)
    for t, c in zip(targets, codes):
        m = full_operator(n, (t,), pauli_matrix(c)) @ m
    return m
