"""Logical-axis sharding (MaxText-style) for params, activations and caches.

Every parameter/activation carries a tuple of *logical* axis names; a rule
table maps logical names to mesh axes. The builder is divisibility-aware:
GSPMD rejects explicit shardings on non-divisible dims (verified in this
container), so a rule that doesn't divide falls back to replication, and a
mesh axis is never used twice within one PartitionSpec (first logical axis
that can take it wins — e.g. batch=1 long-context decode frees the `data`
axis for the KV-cache sequence dim).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` with Auto axis types: the rule table below leaves
    propagation to GSPMD."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

# ---------------------------------------------------------------------------
# Rule table: logical axis -> preferred mesh axes, in priority order.
# "pod" is a pure data-parallel axis; it only ever shards `batch`.
# ---------------------------------------------------------------------------
DEFAULT_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("batch", ("pod", "data")),
    ("embed", ("data",)),          # FSDP/ZeRO-3 dim of weight matrices
    ("vocab", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("ffn", ("model",)),
    ("experts", ("model",)),
    ("expert_embed", ("data",)),   # FSDP dim of expert matrices (gathered
                                   # per-layer at use — ZeRO-3 semantics)
    ("expert_ffn", ()),            # alt TP dim of expert matrices; route the
                                   # decode path to "gather tokens" when set
    ("d_inner", ("model",)),       # SSM channel dim
    ("cache_seq", ("data",)),      # KV-cache seq; only wins when batch can't
    ("seq_mp", ("model",)),        # sequence-parallel regions (MoE dispatch)
    ("classes", ()),
    ("unembed_d", ()),             # d-dim of the fused unembed/entropy
                                   # contraction; -> ("data",) turns the
                                   # table d-gather into partial-logit psums
    ("layers", ()),
    ("seq", ()),
    ("kv_seq", ()),                # K/V seq dim: keep replicated under
                                   # sequence parallelism ("gather x once,
                                   # not k and v") unless overridden too
    ("head_dim", ()),
    ("kv_lora", ()),
    ("q_lora", ()),
    ("state", ()),
    ("conv", ()),
    ("act_embed", ()),             # activation embed dim: replicated (TP)
)


def rules_dict(overrides: Optional[dict] = None) -> dict:
    d = {k: tuple(v) for k, v in DEFAULT_RULES}
    if overrides:
        for k, v in overrides.items():
            d[k] = tuple(v) if v else ()
    return d


@dataclasses.dataclass(frozen=True)
class AbstractParam:
    """Pytree leaf standing in for a parameter during abstract init.

    Carries shape/dtype (for ShapeDtypeStruct) and logical axes (for
    sharding). Never allocates.
    """
    shape: Tuple[int, ...]
    dtype: Any
    logical_axes: Tuple[Optional[str], ...]

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (
            f"shape {self.shape} vs axes {self.logical_axes}")


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    shape: Sequence[int],
                    mesh: Mesh,
                    rules: Optional[dict] = None) -> P:
    """Build a PartitionSpec honoring divisibility and axis-uniqueness."""
    rules = rules or rules_dict()
    mesh_axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set = set()
    spec = []
    for dim, name in zip(shape, logical_axes):
        assigned: Tuple[str, ...] = ()
        if name is not None:
            remaining = dim
            picked = []
            for mesh_axis in rules.get(name, ()):
                if mesh_axis in used or mesh_axis not in mesh_axis_sizes:
                    continue
                size = mesh_axis_sizes[mesh_axis]
                if remaining % size == 0:
                    picked.append(mesh_axis)
                    used.add(mesh_axis)
                    remaining //= size
            assigned = tuple(picked)
        if len(assigned) == 0:
            spec.append(None)
        elif len(assigned) == 1:
            spec.append(assigned[0])
        else:
            spec.append(assigned)
    return P(*spec)


def sharding_for(leaf: AbstractParam, mesh: Mesh,
                 rules: Optional[dict] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(leaf.logical_axes, leaf.shape,
                                               mesh, rules))


def tree_shardings(tree: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    """Map a pytree of AbstractParam to NamedShardings (opt states reuse it)."""
    return jax.tree.map(
        lambda l: sharding_for(l, mesh, rules),
        tree, is_leaf=lambda x: isinstance(x, AbstractParam))


def tree_shape_structs(tree: Any) -> Any:
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
        tree, is_leaf=lambda x: isinstance(x, AbstractParam))


def constrain(x, logical_axes: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None, rules: Optional[dict] = None):
    """with_sharding_constraint via logical axes; no-op without a mesh."""
    if mesh is None or len(mesh.devices.ravel()) <= 1:
        return x
    spec = logical_to_spec(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Carries the mesh + axis names through model code.

    mesh=None means single-device execution: all collectives/constraints
    become no-ops and MoE uses the local (non-all-to-all) path.
    """
    mesh: Optional[Mesh] = None
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: Optional[str] = None
    rules: Optional[dict] = None

    @property
    def model_parallel_size(self) -> int:
        if self.mesh is None:
            return 1
        return dict(zip(self.mesh.axis_names,
                        self.mesh.devices.shape)).get(self.model_axis, 1)

    def constrain(self, x, logical_axes):
        return constrain(x, logical_axes, self.mesh, self.rules)


def param_count(tree: Any) -> int:
    """Total parameter count; works on real arrays and AbstractParams."""
    leaves = jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, AbstractParam))
    total = 0
    for l in leaves:
        shape = l.shape
        total += int(np.prod(shape)) if shape else 1
    return total
