"""JAX/XLA backend: pipeline + IR emission + jit behind the Backend API.

Code generation itself lives in :mod:`repro.transformers.jax_backend`
(the emitter table); this module is the sanctioned entry that composes it
with the pass pipeline, sharding options, and the compile cache.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

from ..core.function import Function
from ..transformers.jax_backend import EmitCtx, emit_callable
from .base import Backend, register_backend
from .options import CompileOptions


@register_backend
class JaxBackend(Backend):
    """Compiles IR -> jitted XLA executable (optionally pjit-partitioned)."""

    name = "jax"
    default_level = "O1"

    def __init__(self, **backend_opts):
        device = backend_opts.pop("device", None)
        if backend_opts:
            raise TypeError(
                f"unknown jax backend opts {sorted(backend_opts)}; "
                f"supported: ['device']")
        self.device = (self._resolve_device(device)
                       if device is not None else None)
        opts = {}
        if self.device is not None:
            # normalize to "platform:id" whichever spelling the caller
            # used, so the instance memo and the disk-cache entry key see
            # one stable string per physical device
            opts["device"] = f"{self.device.platform}:{self.device.id}"
        super().__init__(**opts)

    @staticmethod
    def _resolve_device(spec):
        """``device=`` opt -> a concrete ``jax.Device``.

        Accepts a ``jax.Device``, an index into ``jax.devices()``, or a
        ``"platform[:index]"`` string (``"cpu"``, ``"cpu:1"``, ``"gpu:0"``).
        Unknown ids fail here, at ``Backend.create`` time, with the
        available devices listed — not at first dispatch."""
        import jax

        devices = jax.devices()
        avail = [f"{d.platform}:{d.id}" for d in devices]
        if isinstance(spec, jax.Device):
            if spec not in devices:
                raise ValueError(
                    f"device {spec!r} is not attached; available: {avail}")
            return spec
        if isinstance(spec, (int, np.integer)) \
                and not isinstance(spec, bool):
            if not 0 <= int(spec) < len(devices):
                raise ValueError(
                    f"device index {int(spec)} out of range; "
                    f"available: {avail}")
            return devices[int(spec)]
        if isinstance(spec, str):
            plat, _, idx_s = spec.lower().partition(":")
            if idx_s and not idx_s.isdigit():
                raise ValueError(
                    f"malformed device {spec!r} (want 'platform[:index]'); "
                    f"available: {avail}")
            idx = int(idx_s) if idx_s else 0
            matches = [d for d in devices if d.platform.lower() == plat]
            if idx < len(matches):
                return matches[idx]
            raise ValueError(
                f"unknown device {spec!r}; available: {avail}")
        raise TypeError(
            f"device must be a jax.Device, int index, or "
            f"'platform[:index]' string, got {type(spec).__name__}")

    def _codegen(self, fn: Function, options: CompileOptions
                 ) -> Tuple[Callable, Optional[Callable], Optional[Callable]]:
        import jax

        ctx = EmitCtx(mode=options.mode, mesh=options.mesh,
                      use_pallas=options.use_pallas,
                      remat_scan=options.remat_scan,
                      interpret_pallas=options.interpret_pallas,
                      attn_impl=options.attn_impl,
                      attn_chunk=options.attn_chunk,
                      mm_bm=options.mm_bm, mm_bn=options.mm_bn,
                      mm_bk=options.mm_bk,
                      axis_rules=options.axis_rules)
        run = emit_callable(fn, ctx)
        mesh = self._shardmap_mesh(fn, options)
        if mesh is not None:
            run = self._wrap_shard_map(run, fn, mesh)
        lower = None
        if options.static_jit:
            kw = {}
            if options.in_shardings is not None:
                kw["in_shardings"] = options.in_shardings
            if options.out_shardings is not None:
                kw["out_shardings"] = options.out_shardings
            if self.device is not None and "out_shardings" not in kw \
                    and mesh is None:
                # pin via a single-device output sharding (the supported
                # spelling — jit's `device=` kwarg is deprecated): inputs
                # follow the outputs' placement, so donated KV chains
                # stay resident on the pinned device
                kw["out_shardings"] = \
                    jax.sharding.SingleDeviceSharding(self.device)
            run = jax.jit(run, donate_argnums=options.donate_argnums, **kw)
            lower = run.lower

        def call(*args):
            return [np.asarray(o) for o in run(*args)]

        return call, run, lower

    @staticmethod
    def _shardmap_mesh(fn: Function, options: CompileOptions):
        """The mesh to shard_map a partitioned graph over, or None.

        Active only when the PartitionGraph pass actually ran (parameters
        carry ``pspec`` attrs) — plain ``mode='shardmap'`` compiles with
        hand-written collectives (tests, manual wraps) are left alone."""
        if options.mode != "shardmap":
            return None
        if not any("pspec" in p.attrs for p in fn.parameters):
            return None
        from .sharding import mesh_for_options
        return mesh_for_options(options)

    @staticmethod
    def _wrap_shard_map(run: Callable, fn: Function, mesh):
        """Wrap the emitted callable in shard_map with the specs the
        partition pass stamped on the graph.  Callers keep passing global
        arrays; jit splits them per ``in_specs`` (and donation keeps the
        sharded KV chain device-resident across dispatches)."""
        import jax
        from jax.sharding import PartitionSpec

        from .sharding import partition_spec

        in_specs = tuple(partition_spec(p) for p in fn.parameters)
        out_specs = []
        for r in fn.results:
            ps = r.node.attrs.get("out_pspecs")
            spec = ps[r.index] if ps else (None,) * len(r.shape)
            out_specs.append(PartitionSpec(*spec))

        def as_tuple(*args):
            return tuple(run(*args))

        wrapped = jax.shard_map(as_tuple, mesh=mesh, in_specs=in_specs,
                                out_specs=tuple(out_specs), check_vma=False)

        def as_list(*args):
            return list(wrapped(*args))

        return as_list

    # -- persistent-cache AOT hooks ------------------------------------------
    def _exportable(self, options: CompileOptions) -> bool:
        """AOT serialization covers the plain single-device jit path only:
        meshes/shardings don't rehydrate portably, an exported module
        drops donation (a donated hot loop must re-jit from the graph),
        and a blob loaded on a device-pinned backend would silently run
        on the default device instead of the pinned one."""
        return (self.device is None
                and options.static_jit and options.mode == "jit"
                and options.mesh is None and options.in_shardings is None
                and options.out_shardings is None
                and not options.donate_argnums)

    def _export_executable(self, compiled, options: CompileOptions
                           ) -> Optional[bytes]:
        if not self._exportable(options):
            return None
        try:
            import jax
            from jax import export as jexport

            specs = [jax.ShapeDtypeStruct(t.shape, np.dtype(t.dtype))
                     for t in compiled.function.in_types]
            return jexport.export(compiled.raw)(*specs).serialize()
        except Exception:
            return None  # best-effort: the graph entry alone is still a win

    def _load_executable(self, data: bytes, fn: Function,
                         options: CompileOptions):
        if not self._exportable(options):
            return None
        try:
            import jax
            from jax import export as jexport

            exported = jexport.deserialize(bytearray(data))
            # a blob lowered on another platform (cache dir shared between
            # a GPU box and a CPU CI runner) would only fail at first call,
            # inside the serve loop — reject it here and fall back to
            # re-emitting from the stored graph instead
            platforms = {p.lower() for p in exported.platforms}
            if jax.default_backend().lower() not in platforms:
                return None
            run = jax.jit(exported.call)

            def call(*args):
                return [np.asarray(o) for o in run(*args)]

            return call, run, run.lower
        except Exception:
            return None  # stale/alien blob: re-emit from the stored graph
