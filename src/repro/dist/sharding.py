"""Logical-axis sharding rules — the layer every pspec in the system flows
through.

Model code names tensor dimensions *logically* (``batch``, ``kv_heads``,
``mlp``, ...).  A ruleset maps each logical name to one mesh axis (str),
one joint axis group (tuple), or None; ``logical_to_pspec`` resolves the
names of one tensor against a concrete (or abstract) mesh, enforcing the
invariants the partitioner requires:

* axes the mesh lacks are dropped from the group, so a rule like
  ``batch -> ("pod", "data")`` spans both data-parallel axes on a
  multi-pod mesh and degrades transparently to ``data`` alone on one pod;
* a mesh axis is consumed by at most one dimension of the tensor;
* a dimension is only sharded if its size is divisible by the product of
  the remaining axes' sizes — trailing axes are shed until it divides,
  and the dimension replicates when none fit (the GQA fallback: 4 KV
  heads on an 8-way model axis must replicate, not crash).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec

# logical name -> mesh axis (str), axis group (tuple, applied jointly), or
# None (never shard).  Names absent from the ruleset replicate.  Callers
# override per-cell with plain ``dict(base, kv_seq="model", head_dim=None)``.
RuleValue = Optional[object]  # None | str | Tuple[str, ...]
Rules = Dict[str, RuleValue]

DEFAULT_RULES: Rules = {
    # data-parallel batch: spans pod+data on a multi-pod mesh; axes missing
    # from the mesh are dropped, so one rule serves both topologies.
    "batch": ("pod", "data"),
    "seq": None,                 # replicated in the default (TP) layout
    "kv_seq": None,
    "embed": None,               # activations/residual dim stays replicated
    "head_dim": None,
    "qdh": None,
    "layers": None,              # scan-stacked layer dim is never sharded
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "d_inner": "model",          # SSM/xLSTM inner dim
    "ssm_heads": "model",
}

# FSDP-style parameter layout: weights also shard their non-TP dim over the
# data axis so each replica holds 1/|data| of the parameters.
FSDP_RULES: Rules = {
    **DEFAULT_RULES,
    "embed": "data",
}

# Optimizer moments follow the FSDP parameter layout (they are per-parameter
# state and never participate in TP matmuls directly).
MOMENTS_RULES: Rules = {
    **FSDP_RULES,
}

# Decode: tiny per-step batches; batch on data alone, heads on model, and
# the KV sequence dimension replicated (paged pools shard physically).
DECODE_RULES: Rules = {
    **DEFAULT_RULES,
    "batch": "data",
}

# Sequence-parallel decode: long-context shards the KV sequence over the
# model axis (ring attention); KV head dims then replicate.
SP_DECODE_RULES: Rules = {
    **DECODE_RULES,
    "kv_seq": "model",
    "kv_heads": None,
}


_ACTIVE_RULES = threading.local()


@contextlib.contextmanager
def use_rules(rules: Rules):
    """Make ``rules`` the ambient ruleset for ``constrain`` and for
    ``logical_to_pspec(..., rules=None)`` within the block."""
    stack = getattr(_ACTIVE_RULES, "stack", None)
    if stack is None:
        stack = _ACTIVE_RULES.stack = []
    stack.append(rules)
    try:
        yield rules
    finally:
        stack.pop()


def active_rules() -> Rules:
    stack = getattr(_ACTIVE_RULES, "stack", None)
    return stack[-1] if stack else DEFAULT_RULES


def _axis_sizes(mesh) -> Dict[str, int]:
    # Mesh.shape and AbstractMesh.shape are both name->size mappings.
    return dict(mesh.shape)


def _shed_until_divisible(axes, axis_sizes, size):
    """THE divisibility fallback: drop trailing axes until ``size`` divides
    the product of the remainder; shedding everything replicates (the GQA
    fallback).  Shared by ``logical_to_pspec`` and ``batch_data_axes`` so
    the rule cannot drift between the pspec resolver and the shard_map
    paths that mirror it."""
    axes = list(axes)
    while axes:
        total = 1
        for a in axes:
            total *= axis_sizes[a]
        if total > 0 and size % total == 0:
            break
        axes.pop()
    return axes


def logical_to_pspec(
    names: Sequence[Optional[str]],
    sizes: Sequence[int],
    mesh,
    rules: Optional[Rules] = None,
) -> PartitionSpec:
    """Resolve logical dimension names to a PartitionSpec on ``mesh``.

    ``names[i]`` may be None (always replicated).  ``rules=None`` uses the
    ambient ruleset (``use_rules``), falling back to ``DEFAULT_RULES``.
    Works with both ``Mesh`` and ``AbstractMesh`` — only axis names and
    sizes are consulted.
    """
    if len(names) != len(sizes):
        raise ValueError("names and sizes must have equal length")
    if rules is None:
        rules = active_rules()
    axis_sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for name, size in zip(names, sizes):
        value = rules.get(name) if name is not None else None
        if value is None:
            axes = []
        elif isinstance(value, str):
            axes = [value]
        else:
            axes = list(value)
        # Axes the mesh lacks, or that an earlier dim consumed, drop out —
        # the same rule serves meshes of different topology.
        axes = [a for a in axes if a in axis_sizes and a not in used]
        axes = _shed_until_divisible(axes, axis_sizes, size)
        used.update(axes)
        entries.append(
            tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return PartitionSpec(*entries)


def _active_mesh():
    """The mesh installed by ``with mesh:``, or None outside any context."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def active_mesh():
    """Public accessor for the ambient ``with mesh:`` context (or None).
    Model code uses it to decide whether an explicit shard_map path (the
    ragged ep MoE dispatch) applies."""
    return _active_mesh()


def constrain(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names.

    Model code annotates activations with logical names; under an active
    mesh context the names resolve through the ambient ruleset, and outside
    any mesh (single-device tests, CPU smoke runs) this is the identity.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = logical_to_pspec(names, x.shape, mesh, active_rules())
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def batch_data_axes(mesh, size: Optional[int] = None) -> Tuple[str, ...]:
    """The data-parallel axes of ``mesh`` (``pod``+``data`` when present)
    that can shard a dimension of ``size``: trailing axes are shed until
    the size divides evenly, the same fallback ``logical_to_pspec``
    applies.  ``size=None`` skips the divisibility check.  This is THE
    definition of which mesh axes carry the batch — the shard_map MoE ep
    path and the data pipeline both resolve through it."""
    sizes = _axis_sizes(mesh)
    axes = [a for a in ("pod", "data") if a in sizes]
    if size is not None:
        axes = _shed_until_divisible(axes, sizes, size)
    return tuple(axes)


def batch_pspec(mesh) -> PartitionSpec:
    """PartitionSpec sharding dim 0 over the data-parallel axes of ``mesh``
    (pod+data when present).  Used by the data pipeline for host batches."""
    axes = batch_data_axes(mesh)
    if not axes:
        return PartitionSpec()
    return PartitionSpec(axes if len(axes) > 1 else axes[0])


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """A device-free ``AbstractMesh`` of the given axes — tests and tooling
    exercise the rule resolver on it without devices."""
    return jax.sharding.AbstractMesh(tuple(int(s) for s in axis_shapes),
                                     tuple(axis_names))
