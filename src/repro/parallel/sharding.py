"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Mesh axes:
  single-pod : ('data', 'model')           = (16, 16)
  multi-pod  : ('pod', 'data', 'model')    = (2, 16, 16)

Logical axis names appear in param/activation descriptors; `rules` maps them
to mesh axes. GSPMD handles uneven dims (25 heads on a 16-way axis, vocab
32001, ...) by padding internally — configs additionally pad vocab where it
is nearly free (see configs/registry.py).

Parameters are FSDP-sharded (ZeRO-3 style) over the 'data' axis (optionally
('pod','data')) on their largest replicated dim via the 'fsdp' logical axis,
and tensor-parallel over 'model' on heads/mlp/vocab/experts dims.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import (AbstractMesh, Mesh, NamedSharding,
                          PartitionSpec as PS)

Axes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    batch: Axes = ("pod", "data")     # activation batch dim
    seq: Axes = None                  # activation sequence dim (SP option)
    embed: Axes = None                # activation embed dim
    heads: Axes = "model"             # attention heads (TP)
    kv_heads: Axes = "model"
    head_dim: Axes = None
    mlp: Axes = "model"               # ffn hidden (TP)
    vocab: Axes = "model"             # embedding/logits vocab (TP)
    experts: Axes = "model"           # MoE experts (EP)
    fsdp: Axes = "data"               # param sharding axis (ZeRO-3)
    layers: Axes = None               # scan-stacked layer axis
    kv_lora: Axes = None              # MLA compressed dim
    conv_io: Axes = None              # conv in/out channels
    stage: Axes = None                # optional pipeline axis

    def axes_for(self, name: Optional[str], mesh: Mesh) -> Axes:
        if name is None:
            return None
        ax = getattr(self, name)
        if ax is None:
            return None
        if isinstance(ax, str):
            return ax if ax in mesh.axis_names else None
        pruned = tuple(a for a in ax if a in mesh.axis_names)
        return pruned if pruned else None

    def spec(self, logical: Tuple[Optional[str], ...], mesh: Mesh) -> PS:
        """PartitionSpec from a tuple of logical dim names (None = replicated
        dim). Drops mesh axes that are already taken by an earlier dim."""
        used = set()
        parts = []
        for name in logical:
            ax = self.axes_for(name, mesh)
            if ax is None:
                parts.append(None)
                continue
            tup = (ax,) if isinstance(ax, str) else ax
            tup = tuple(a for a in tup if a not in used)
            if not tup:
                parts.append(None)
                continue
            used.update(tup)
            parts.append(tup[0] if len(tup) == 1 else tup)
        while parts and parts[-1] is None:
            parts.pop()
        return PS(*parts)

    def sharding(self, logical, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical, mesh))


DEFAULT_RULES = ShardingRules()

# Sequence-parallel variant: activations sharded on seq between blocks (used
# for long-context cells to bound per-device activation memory).
SEQ_PARALLEL_RULES = dataclasses.replace(DEFAULT_RULES, seq="model")

# FSDP over both pod and data (ZeRO across all data-parallel replicas).
WIDE_FSDP_RULES = dataclasses.replace(DEFAULT_RULES, fsdp=("pod", "data"))


def prune_spec(shape, spec: PS, mesh: Mesh) -> PS:
    """Drop mesh axes whose size does not evenly divide the dim they shard.

    Explicit input shardings (unlike internal GSPMD constraints) must divide
    evenly; uneven dims (25 heads, 2-block quantizer scales, ...) fall back
    to replication on that dim.

    A mesh axis may shard at most one dim: when a spec names the same axis
    on two dims (e.g. hand-written PS('model', 'model')), only the first
    occurrence is kept — same first-dim-wins rule as `ShardingRules.spec`.
    The duplicate used to survive into the pruned spec, and NamedSharding
    rejects it only at device_put time with an opaque XLA error."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    parts = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            parts.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        keep = []
        remaining = shape[i]
        for a in axes:
            if a not in used and remaining % sizes[a] == 0:
                keep.append(a)
                used.add(a)
                remaining //= sizes[a]
        parts.append(tuple(keep) if len(keep) > 1 else
                     (keep[0] if keep else None))
    while parts and parts[-1] is None:
        parts.pop()
    return PS(*parts)


def pruned_sharding(shape, spec: PS, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, prune_spec(shape, spec, mesh))


def constrain(x, rules: ShardingRules, *logical):
    """with_sharding_constraint using logical names; no-op off-mesh."""
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, rules.sharding(logical, mesh))


def mesh_axis_size(axis: str) -> int:
    m = _current_mesh()
    if m is None or axis not in m.axis_names:
        return 1
    return m.shape[axis]


def _current_mesh() -> Optional[AbstractMesh]:
    """The ambient mesh installed by ``jax.set_mesh``, or None.

    Inside a shard_map body the ambient mesh has manual axes: the body is
    per-device code and takes no sharding constraints, so it sees none."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty or m.manual_axes else m
