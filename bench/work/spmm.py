"""Work of one ``X(i,j) = B(i,k) * C(k,j)`` request, B and C sparse.

FLOPs: a multiply and an add per product the expression needs,
``2 sum_k nnz(B(:,k)) nnz(C(k,:))``. Minimum bytes: both inputs once
(each nonzero's 4-byte value and 4-byte coordinate, and ``rows + 1``
4-byte row pointers) and the output's nonzeros once, in the same form.
"""
import numpy as np
import scipy.sparse as sp

VALUE = COORD = 4


def _csr_bytes(nnz, rows):
    return nnz * (VALUE + COORD) + (rows + 1) * COORD


def _pattern(op):
    rows, cols, _ = op.coo
    return sp.csr_matrix((np.ones(len(rows)), (rows, cols)), shape=op.shape)


def work(ops):
    B, C = ops["B"], ops["C"]
    k = B.shape[1]
    per_k_b = np.bincount(B.coo[1], minlength=k).astype(np.int64)
    per_k_c = np.bincount(C.coo[0], minlength=k).astype(np.int64)
    flops = 2 * int(per_k_b @ per_k_c)
    # counts of products are positive, so no output entry cancels
    out_nnz = (_pattern(B) @ _pattern(C)).nnz
    nbytes = _csr_bytes(len(B.coo[0]), B.shape[0]) \
        + _csr_bytes(len(C.coo[0]), C.shape[0]) \
        + _csr_bytes(out_nnz, B.shape[0])
    return flops, nbytes
