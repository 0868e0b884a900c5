"""Bytes an application of a circuit has to move, from its shapes alone."""


def application_bytes(state_bytes: int) -> int:
    """One read and one write of the whole register: the least any
    application of a circuit that touches every amplitude can move."""
    return 2 * state_bytes
