"""The one sharding API: policies, meshes, and partition profiles.

Everything distribution-related that callers used to assemble from three
modules (``runtime/distributed.py`` policies, ``launch/mesh.py`` mesh
construction, ``launch/shardings.py`` per-graph glue) lives here, next to
:class:`~repro.backend.options.CompileOptions` — the object that actually
consumes it.  Graphs carry *logical* axis names (builders tag every
parameter and input); this module maps them onto mesh axes, either as
pjit PartitionSpecs (``graph_shardings``/``train_step_shardings``) or as
the per-logical-axis rule table the :class:`PartitionGraph` pass uses to
cut a graph into per-device programs (``partition_profile``).

The old modules remain as one-release deprecation shims re-exporting
from here (policed by ``scripts/check_deprecated.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class ParamInfo:
    """Logical description of one parameter tensor."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any
    logical_axes: Tuple[Optional[str], ...]  # one entry per dim


# logical axis -> mesh axes, per policy profile
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),  # batch dims (pod filtered out on 1-pod mesh)
    "vocab": ("model",),
    "embed": ("zero",),        # ZeRO/FSDP shard of the embedding dim
    "ffn": ("model",),         # TP shard of the hidden dim
    "heads": ("model",),
    "kv_heads": (),            # few kv heads: keep replicated
    "kv_seq": ("model",),      # decode KV caches: sequence-shard on model
    "experts": ("expert",),    # resolved to real axes by the profile
    "expert_ffn": (),
    "layers": (),              # stacked-layer leading dim stays unsharded
    "conv": (),
    "seq": (),
    "state": (),
    None: (),
}


@dataclasses.dataclass
class ShardingPolicy:
    """Maps logical axes to mesh axes and produces PartitionSpecs."""

    rules: Dict[str, Tuple[str, ...]]
    zero_axes: Tuple[str, ...] = ("data",)   # FSDP axes for 'embed'-tagged dims
    expert_axes: Tuple[str, ...] = ("model",)
    batch_axes: Tuple[str, ...] = ("data",)  # + 'pod' when present

    def resolve(self, logical: Optional[str]) -> Tuple[str, ...]:
        axes = self.rules.get(logical, ())
        out = []
        for a in axes:
            if a == "expert":
                out.extend(self.expert_axes)
            elif a == "zero":
                out.extend(self.zero_axes)
            else:
                out.append(a)
        return tuple(out)

    def spec_for(self, info: ParamInfo, mesh) -> "jax.sharding.PartitionSpec":
        from jax.sharding import PartitionSpec

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        used = set()
        entries: List[Any] = []
        for dim, logical in zip(info.shape, info.logical_axes):
            axes = [a for a in self.resolve(logical)
                    if a in sizes and a not in used]
            # keep only axes that divide the dim evenly
            keep: List[str] = []
            prod = 1
            for a in axes:
                if dim % (prod * sizes[a]) == 0:
                    keep.append(a)
                    prod *= sizes[a]
            used.update(keep)
            if not keep:
                entries.append(None)
            elif len(keep) == 1:
                entries.append(keep[0])
            else:
                entries.append(tuple(keep))
        return PartitionSpec(*entries)

    def sharding_for(self, info: ParamInfo, mesh):
        from jax.sharding import NamedSharding

        return NamedSharding(mesh, self.spec_for(info, mesh))

    def batch_spec(self, mesh, rank: int = 2):
        """Batch tensors: leading dim over (pod+)data axes."""
        from jax.sharding import PartitionSpec

        axes = tuple(a for a in ("pod",) + tuple(self.batch_axes)
                     if a in mesh.axis_names)
        axes = tuple(dict.fromkeys(axes))
        lead = axes if len(axes) > 1 else (axes[0] if axes else None)
        return PartitionSpec(lead, *([None] * (rank - 1)))

    def replicated(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(mesh, PartitionSpec())

    def as_rules(self) -> Dict[str, Tuple[str, ...]]:
        """Flat logical->mesh-axes table for the ShardingConstraint
        emitter (jax_backend): every known logical name, resolved."""
        return {k: self.resolve(k) for k in self.rules if k is not None}

    def input_sharding(self, mesh, shape, logical_spec):
        """NamedSharding for a data input from its logical per-dim spec."""
        info = ParamInfo("_input", tuple(shape), None, tuple(logical_spec))
        return self.sharding_for(info, mesh)


def policy_for(profile: str = "default", mesh=None) -> ShardingPolicy:
    """Profiles implement per-arch parallelism mixes (DESIGN.md sec. 5)."""
    rules = dict(DEFAULT_RULES)
    if profile == "default":
        return ShardingPolicy(rules)
    if profile == "zero3_pod":
        # shard the FSDP ('embed') dims across pods too: ZeRO-3 over all chips
        return ShardingPolicy(rules, zero_axes=("pod", "data"))
    if profile == "expert_parallel":
        # MoE: experts across data*model (EP), used when E divides the product
        return ShardingPolicy(rules, expert_axes=("data", "model"))
    if profile == "zero3_pod_ep":
        # deepseek-v3: ZeRO-3 across pods + 256-way expert parallelism
        return ShardingPolicy(rules, zero_axes=("pod", "data"),
                              expert_axes=("data", "model"))
    if profile == "expert_tp":
        # MoE with few experts: shard inside each expert instead
        rules["experts"] = ()
        rules["expert_ffn"] = ("model",)
        return ShardingPolicy(rules)
    raise KeyError(f"unknown sharding profile {profile}")


# per-arch parallelism profile (DESIGN.md sec. 5)
ARCH_PROFILES: Dict[str, str] = {
    "deepseek-v3-671b": "zero3_pod_ep",
    "mixtral-8x22b": "expert_tp",
}


def policy_for_arch(arch_name: str) -> ShardingPolicy:
    return policy_for(ARCH_PROFILES.get(arch_name, "default"))


def infos_to_shardings(policy: ShardingPolicy, infos: Sequence[ParamInfo], mesh):
    return [policy.sharding_for(i, mesh) for i in infos]


# ---------------------------------------------------------------------------
# partition profiles: the PartitionGraph pass's view of a policy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PartitionProfile:
    """What the :class:`~repro.core.passes.partition.PartitionGraph` pass
    needs from ``CompileOptions(partition=..., mesh_shape=...)``: mesh
    axis names (positional, matching ``mesh_shape``), a single-mesh-axis
    rule per logical axis, and whether parameter sharding is restricted
    to each weight's *output* (last) dim.

    ``last_dim_only=True`` is the serving tensor-parallel plan: only
    column-parallel weight shards (wq/wk/wv/w_gate/w_up on their output
    dim, rank-1 biases), with AllGather at the transitions back to
    replicated weights — no contraction is split, so every op computes
    the single-device graph's math.  The compiler may still order a
    reduction differently for the per-device shapes: greedy streams
    agree across ``tp`` except where they part at a near-tie of the
    logits (seen on a TPU v5e at deepseek-7b widths).  Row-parallel
    cuts (AllReduce after the matmul) remain available to profiles with
    ``last_dim_only=False``; they re-round split bf16 contractions and
    so trade exactness for halved activations.
    """

    name: str
    axes: Tuple[str, ...]                 # mesh axis names, one per mesh dim
    rules: Dict[str, str]                 # logical axis -> mesh axis
    last_dim_only: bool = False
    # logical axes exempt from the last-dim restriction (e.g. 'kv_heads',
    # which tags an interior dim of the paged KV pool buffers)
    anywhere: Tuple[str, ...] = ()

    def axis_sizes(self, mesh_shape: Sequence[int]) -> Dict[str, int]:
        if len(mesh_shape) != len(self.axes):
            raise ValueError(
                f"partition profile {self.name!r} has axes {self.axes} "
                f"but mesh_shape {tuple(mesh_shape)}")
        return dict(zip(self.axes, (int(s) for s in mesh_shape)))


def _policy_pass_rules(policy: ShardingPolicy,
                       mesh_axes: Tuple[str, ...]) -> Dict[str, str]:
    """Flatten a pjit policy to the pass's one-axis-per-logical table
    (the pass shards each dim over at most one mesh axis).  Resolved
    axes outside the profile's mesh (e.g. 'pod' on a (data, model)
    mesh) are dropped, not blindly taken first."""
    out = {}
    for logical in policy.rules:
        if logical is None:
            continue
        axes = [a for a in policy.resolve(logical) if a in mesh_axes]
        if axes:
            out[logical] = axes[0]
    return out


def partition_profile(name: str) -> PartitionProfile:
    """Resolve ``CompileOptions.partition`` to a pass profile."""
    if name == "tp":
        return PartitionProfile(
            "tp", axes=("model",),
            rules={"heads": "model", "kv_heads": "model", "ffn": "model"},
            last_dim_only=True, anywhere=("kv_heads",))
    # pjit policy profiles double as shardmap partition profiles on a
    # (data, model) mesh; 'batch' resolves to the data axis
    policy = policy_for(name)  # raises KeyError on unknown names
    rules = _policy_pass_rules(policy, ("data", "model"))
    rules.setdefault("batch", "data")
    return PartitionProfile(name, axes=("data", "model"), rules=rules)


PARTITION_PROFILES: Tuple[str, ...] = (
    "tp", "default", "zero3_pod", "expert_parallel", "zero3_pod_ep",
    "expert_tp")


# ---------------------------------------------------------------------------
# mesh construction (moved from launch/mesh.py)
# ---------------------------------------------------------------------------
def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small fake-device meshes)."""
    import jax

    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: Optional[int] = None):
    """Mesh over whatever devices exist (smoke tests: 1 CPU)."""
    import jax

    n = len(jax.devices())
    mp = model_parallel or 1
    return jax.make_mesh((n // mp, mp), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch (pod+data when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_for_options(options) -> Any:
    """The mesh a shardmap/pjit compile runs on: ``options.mesh`` when
    given, else a fresh device mesh of ``options.mesh_shape`` with the
    partition profile's axis names."""
    if options.mesh is not None:
        return options.mesh
    if options.mesh_shape is None:
        return None
    import math

    import jax
    import numpy as np
    from jax.sharding import Mesh

    prof = partition_profile(options.partition or "tp")
    n = math.prod(options.mesh_shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh_shape {options.mesh_shape} needs {n} devices but only "
            f"{len(devs)} are attached (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} for a "
            f"CPU test mesh)")
    return Mesh(np.array(devs[:n]).reshape(options.mesh_shape), prof.axes)


def partition_spec(param) -> Any:
    """The PartitionSpec the PartitionGraph pass stamped on one graph
    parameter (replicated when it stamped none)."""
    from jax.sharding import PartitionSpec

    ps = param.attrs.get("pspec") or (None,) * len(param.out_types[0].shape)
    return PartitionSpec(*ps)


def input_shardings(fn, options) -> Dict[str, Any]:
    """Input name -> ``NamedSharding`` on the options' mesh for a graph
    the PartitionGraph pass cut: where to place each global array once,
    so the shard_map'd program never re-lays it out per dispatch."""
    from jax.sharding import NamedSharding

    mesh = mesh_for_options(options)
    return {p.name: NamedSharding(mesh, partition_spec(p))
            for p in fn.parameters}


# ---------------------------------------------------------------------------
# per-graph pjit glue (moved from launch/shardings.py)
# ---------------------------------------------------------------------------
def param_shardings(builder, mesh, policy: ShardingPolicy):
    out = []
    for name in builder.param_names():
        s = builder.params[name]
        info = ParamInfo(s.name, s.shape, s.dtype, s.logical_axes)
        out.append(policy.sharding_for(info, mesh))
    return out


def data_shardings(builder, mesh, policy: ShardingPolicy):
    out = []
    for node in builder.inputs:
        spec = builder.input_specs[node.name]
        out.append(policy.input_sharding(mesh, node.out_types[0].shape, spec))
    return out


def graph_shardings(graphs, mesh, policy: Optional[ShardingPolicy] = None):
    """(in_shardings, axis_rules) for a prefill/decode graph."""
    policy = policy or policy_for_arch(graphs.cfg.name)
    ins = data_shardings(graphs.builder, mesh, policy) + \
        param_shardings(graphs.builder, mesh, policy)
    return tuple(ins), policy.as_rules()


def train_step_shardings(ts, mesh, policy: Optional[ShardingPolicy] = None):
    """(in_shardings, out_shardings, donate_argnums, axis_rules) for a
    train-step Function: (data..., step, *params, *m, *v) ->
    (loss, *params', *m', *v')."""
    policy = policy or policy_for_arch(ts.graphs.cfg.name)
    b = ts.graphs.builder
    data = data_shardings(b, mesh, policy)
    repl = policy.replicated(mesh)
    pshard = param_shardings(b, mesh, policy)
    ins = tuple(data) + (repl,) + tuple(pshard) * 3
    outs = (repl,) + tuple(pshard) * 3
    n_data = len(data)
    donate = tuple(range(n_data + 1, n_data + 1 + 3 * len(pshard)))
    return ins, outs, donate, policy.as_rules()
