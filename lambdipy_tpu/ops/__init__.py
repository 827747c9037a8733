"""Hot ops: Pallas TPU kernels, each beside a pure-jax reference.

Every op ships two implementations: a Pallas/Mosaic kernel for the TPU hot
path and a pure-jax reference that is the numerics oracle. A kernel
function always builds the kernel: it compiles for the chip unless the
CALLER passes ``interpret=True`` (tests do), and it raises ``ValueError``
for a shape it cannot tile — it never looks at the backend and never
returns the reference in its own name. The model layer chooses between
the two by the one thing it can observe, :func:`kernels_compile_here`.
"""

import jax

from lambdipy_tpu.ops.attention import flash_attention, mha_reference


def kernels_compile_here() -> bool:
    """Mosaic compiles only for a TPU backend; everywhere else the model
    takes the pure-jax reference (the same math, which the kernels' own
    interpret-mode tests hold them to)."""
    return jax.default_backend() == "tpu"


__all__ = ["flash_attention", "kernels_compile_here", "mha_reference"]
