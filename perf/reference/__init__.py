"""Plain references: ``jax.numpy`` in float32 at ``highest`` matmul precision,
computed in row blocks so that they fit beside the data.  Nothing here imports
the program, and nothing here takes what the program has made.

Each reference takes ``dtype``/``precision`` so that the same code, computed
one precision lower, is the control that has to come out as not correct.
"""


def block_rows(rows: int, limit: int) -> int:
    """Rows per block: the largest divisor of ``rows`` up to ``limit``."""
    for cand in range(min(limit, rows), 0, -1):
        if rows % cand == 0:
            return cand
    return rows
