"""Pallas TPU kernels for the compound ops (see ``ops.py`` for selection)."""
import jax


def check_interpret(interpret: bool) -> bool:
    """Interpret mode is for hosts without a TPU, and only when a caller
    asks for it by name: on a TPU a kernel runs compiled or is refused."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "Pallas interpret mode was requested on a TPU; kernels there "
            "run compiled (pass interpret=False)")
    return interpret
