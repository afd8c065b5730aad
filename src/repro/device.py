"""Which JAX backend this process runs on, asked in one place."""
import jax


def on_cpu() -> bool:
    """True when JAX's default backend is the CPU.  Pallas kernels then run
    in the interpreter (the CPU has no Mosaic compiler), and worker
    processes may use JAX; on a chip the device belongs to the one process
    that holds it."""
    return jax.default_backend() == "cpu"
