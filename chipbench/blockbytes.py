"""The least HBM traffic of one coupled block-PCG iteration.

A b-component solve carries x, z = M^-1 r and p for every component from
one iteration to the next, so each is read and written at least once per
iteration: ``6 b E n^3`` words, in float32.  The operator's fields (the
metric diagonals, the Jacobi diagonal) are left out, since a box's can be
generated in the kernel instead of read: no split, fusion or generated
metric moves fewer bytes.  The count is the problem's, kept with the
benchmark, not read from the program.
"""
from __future__ import annotations


def least_bytes_per_iter(components: int, dofs: float,
                         itemsize: int = 4) -> float:
    """Bytes of one iteration for ``components`` fields of ``dofs``
    element-local nodes each (``E n^3``)."""
    return 6.0 * components * dofs * itemsize
