"""A ``sparse`` matrix: ``nnz`` nonzeros at distinct uniform random
positions, values uniform in [-1, 1] (never 0), in the operand's
``dtype`` (float32 by default). Only its COO triplets are drawn, in
row-major order; ``Operand.to_dense`` builds the dense form where an
engine asks for it."""
import numpy as np

from benchlib.operands import Operand


def draw(spec, gen):
    shape = tuple(int(d) for d in spec["shape"])
    if len(shape) != 2:
        raise ValueError(f"a sparse operand is a matrix, not of rank "
                         f"{len(shape)}")
    dtype = np.dtype(spec.get("dtype", "float32"))
    n_rows, n_cols = shape
    flat = np.sort(gen.choice(n_rows * n_cols, size=int(spec["nnz"]),
                              replace=False))
    vals = gen.uniform(-1.0, 1.0, flat.size).astype(dtype)
    vals[vals == 0] = 1
    rows, cols = np.divmod(flat, n_cols)
    return Operand(shape, None, (rows, cols, vals))
