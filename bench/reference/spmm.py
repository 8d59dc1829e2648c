"""Plain reference of ``X(i,j) = B(i,k) * C(k,j)``, a float64 product of
the COO triplets of B and C (``scipy.sparse``); and its control, the
dense product with the operands and the result in bfloat16 on the
default device."""
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp


def _csr(op):
    rows, cols, vals = op.coo
    return sp.csr_matrix((vals.astype(np.float64), (rows, cols)),
                         shape=op.shape)


def reference(ops):
    return (_csr(ops["B"]) @ _csr(ops["C"])).toarray()


def control(ops):
    X = jnp.dot(jnp.asarray(ops["B"].to_dense(), jnp.bfloat16),
                jnp.asarray(ops["C"].to_dense(), jnp.bfloat16),
                preferred_element_type=jnp.bfloat16)
    return np.asarray(X.astype(jnp.float32), np.float64)
