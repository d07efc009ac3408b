"""Pallas kernels of the serving and retrieval paths.  Each kernel has a
pure-jnp reference (``ref.py``) and a jitted wrapper (``ops.py``).

Whether a kernel runs compiled or through the Pallas interpreter is decided
in one place, ``on_backend``: interpreted only in a program lowered for the
CPU, compiled everywhere else.
"""
import jax


def on_backend(build):
    """Pick a kernel's mode when the program is lowered.

    ``build(interpret)`` returns the ``pallas_call`` for one mode; the
    returned function takes the kernel's arguments and lowers
    ``build(True)`` for the CPU and ``build(False)`` for every other
    platform.  The choice follows the platform being compiled for, not the
    process's default backend, so a program compiled for a TPU from a CPU
    host gets the compiled kernel, and no argument a caller leaves out can
    make a kernel run interpreted on a TPU."""

    def call(*args):
        return jax.lax.platform_dependent(
            *args, cpu=build(True), default=build(False)
        )

    return call
