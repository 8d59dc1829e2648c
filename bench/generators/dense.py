"""A ``dense`` operand: uniform in [-1, 1] everywhere, of any rank, in
the operand's ``dtype`` (float32 by default)."""
import numpy as np

from benchlib.operands import Operand


def draw(spec, gen):
    shape = tuple(int(d) for d in spec["shape"])
    dtype = np.dtype(spec.get("dtype", "float32"))
    return Operand(shape, gen.uniform(-1.0, 1.0, shape).astype(dtype))
