"""Transformers: per-backend compilers for the IR (paper sec. 4)."""
from .base import (Executable, Transformer, available_transformers,  # noqa: F401
                   get_transformer, register_transformer)
from . import interpreter as _interp  # noqa: F401  (registers itself)
from . import jax_backend as _jax  # noqa: F401  (registers itself)
