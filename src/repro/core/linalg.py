"""Float32-accurate contractions for the certified path.

On a TPU, XLA runs a float32 matmul as a single bf16 pass unless the op
asks for more, which keeps about three significant digits. Every screening
bound, solver margin, duality gap and ``delta`` radius of the path is built
from such contractions, and an understated bound or ``delta`` discards a
live feature. So every dense contraction of ``core/`` and ``sparse/`` goes
through :func:`dot` at ``Precision.HIGHEST`` (float32 on the chip; on the
CPU float32 matmuls are float32 already, so results there do not move).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse

HIGHEST = jax.lax.Precision.HIGHEST


def dot(a, b):
    """``a @ b`` at float32 precision.

    A ``BCOO`` operand keeps its own ``@``: its contraction is a gather, an
    elementwise product and a segment sum, all exact float32 already.
    """
    if isinstance(a, jsparse.JAXSparse) or isinstance(b, jsparse.JAXSparse):
        return a @ b
    return jnp.matmul(a, b, precision=HIGHEST)
