"""Plain reference of ``x(i) = B(i,j) * c(j)``, from the COO triplets of
B and the dense c, in float64; and its control, the same product with
the operands and the result in bfloat16 on the default device."""
import jax.numpy as jnp
import numpy as np


def reference(ops):
    B, c = ops["B"], ops["c"]
    rows, cols, vals = B.coo
    return np.bincount(rows, weights=vals.astype(np.float64)
                       * c.dense.astype(np.float64)[cols],
                       minlength=B.shape[0])


def control(ops):
    B, c = ops["B"], ops["c"]
    x = jnp.dot(jnp.asarray(B.to_dense(), jnp.bfloat16),
                jnp.asarray(c.dense, jnp.bfloat16),
                preferred_element_type=jnp.bfloat16)
    return np.asarray(x.astype(jnp.float32), np.float64)
