"""The precisions a plain reference can be computed in.

``highest`` (six bf16 passes a product), ``high`` (three) and ``default``
(one; operands rounded to bfloat16, everything else float32) only ask the
backend for fewer passes.  ``bfloat16`` is the computation as a bfloat16 one:
every array it stores goes through bfloat16, products accumulate in float32
as the hardware does.
"""


def matmul(precision):
    """The ``precision=`` a matmul takes under that precision."""
    return "default" if precision == "bfloat16" else precision


def stored(a, precision):
    """``a`` as the precision stores it: through bfloat16 for ``bfloat16``."""
    if precision != "bfloat16":
        return a
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(jnp.float32)
