"""Work of one ``x(i) = B(i,j) * c(j)`` request, B sparse and c dense.

FLOPs: a multiply and an add per nonzero of B, ``2 nnz(B)``. Minimum
bytes: each nonzero of B once (a 4-byte value and a 4-byte column
coordinate), B's row pointers (``rows + 1`` of 4 bytes), c once and x
once (4-byte values).
"""
VALUE = COORD = 4


def work(ops):
    B, c = ops["B"], ops["c"]
    rows = B.shape[0]
    nnz = len(B.coo[2])
    nbytes = nnz * (VALUE + COORD) + (rows + 1) * COORD \
        + (c.shape[0] + rows) * VALUE
    return 2 * nnz, nbytes
