# Pallas TPU kernels for the compute hot-spots of the framework.
# Each subpackage ships: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
# ops.py (jit'd public wrapper), ref.py (pure-jnp oracle used by tests).
#
# All kernels follow the paper's predication discipline: ragged tails and
# data-dependent masks are handled by whilelt-style predicates computed
# inside the kernel, never by shape-specialized variants (SVE C1-C3).

import jax


def resolve_interpret(interpret=None) -> bool:
    """The one place a kernel's Pallas ``interpret`` flag is derived: the
    interpreter on the CPU backend, compiled (Mosaic) everywhere else.  A
    kernel that Mosaic refuses therefore raises on the chip instead of
    quietly interpreting.  An explicit bool wins — the ahead-of-time compile
    tests pass ``False`` to lower for a described TPU from a CPU process."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
