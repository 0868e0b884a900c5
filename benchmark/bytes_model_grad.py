"""Applications and bytes one served gradient has to make, from the tape and
the Hamiltonian alone: the same work whatever program implements it (not read
from the program's counter, which states what ITS sweep applies).

An application is one operator applied to one whole register: a read and a
write of every amplitude. The adjoint method (Jones and Gacon,
arXiv:2009.02823) makes, for a tape of P entries of which the first
parameter is entry s (0-based), K parameters and a Hamiltonian of T terms:

- ``forward``: P, |psi> = U_P ... U_1 |0>;
- ``hamiltonian``: T, |lambda> = sum_k c_k P_k |psi>, one a term;
- ``backward``: 2 (P - s), every entry from the first parameter on undone
  on |phi> and on |lambda>;
- ``bracket``: K, one (dU/dtheta)|phi> and its inner product a parameter.

A program that fuses entries makes fewer passes and may read over the share
this count gives it; one that walks gate by gate, as the program does today,
reads what it is away from one read and one write an application.
"""

from reference_grad import parameter_entries


def applications(ops, terms: int) -> dict:
    """The four counts for a tape ``ops`` (``reference.Tape().ops``) and a
    Hamiltonian of ``terms`` Pauli strings."""
    params = parameter_entries(ops)
    first = params[0] if params else len(ops)
    return {"forward": len(ops), "hamiltonian": terms,
            "backward": 2 * (len(ops) - first), "bracket": len(params)}


def gradient_bytes(ops, terms: int, state_bytes: int) -> int:
    """Bytes one lane's gradient moves at one read and one write of the
    register an application."""
    return sum(applications(ops, terms).values()) * 2 * state_bytes
