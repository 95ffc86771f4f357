"""Pallas TPU kernels for the split path's hot spots (conv, int8 codec)
plus the seed's sequence kernels, with pure-jnp oracles in ``ref.py`` and
jit'd public wrappers in ``ops.py``."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when the
    default platform is the CPU (no Mosaic there).  On any accelerator the
    kernels compile -- nothing falls back to the interpreter.  Read at call
    time and passed on as the kernels' static ``interpret`` argument, so a
    jit never reuses a trace made for another platform."""
    return jax.default_backend() == "cpu"
