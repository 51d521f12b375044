"""The unified compilation API (paper sec. 4, redesigned).

    from repro.backend import Backend, CompileOptions

    be = Backend.create("jax")                     # or "interpreter"
    cf = be.compile(fn, CompileOptions(level="O2"))
    outs = cf(*arrays)            # positional, or cf(x=..., w=...)
    cf.report.summary()           # the pass-pipeline report
    cf.memory_plan, cf.cost       # arena plan + FLOPs/bytes estimate
    be.cache_stats()              # compile-cache hits/misses

Repeated ``compile`` calls with a structurally-identical Function and equal
options are cache hits (keyed on ``Function.signature()`` + the options).
The legacy ``repro.transformers.get_transformer`` path is a deprecated
shim over this module and will be removed after one release.
"""
from .base import (Backend, CacheStats, available_backends,  # noqa: F401
                   register_backend)
from .compiled import CompiledFunction  # noqa: F401
from .diskcache import DiskCompileCache  # noqa: F401
from .options import CompileOptions, OptionsError  # noqa: F401
from . import interpreter as _interpreter  # noqa: F401  (registers itself)
from . import jax_backend as _jax_backend  # noqa: F401  (registers itself)
