"""Logical-axis sharding (MaxText-style) with divisibility-aware resolution.

Models annotate tensors with LOGICAL axis names ("embed", "mlp", "heads",
"experts", "batch", ...). A rule table maps logical axes to mesh axes; the
resolver drops any mapping whose mesh-axis size does not divide the tensor
dimension (e.g. paligemma's kv=1 head on a 16-way model axis, musicgen's 24
heads, hymba's 32001 vocab) — GSPMD correctness never depends on the rules,
only efficiency does.

``axis_rules(...)`` installs a rule table in a context; ``shard(x, *axes)``
applies a with_sharding_constraint when a mesh is active, else no-ops, so the
same model code runs single-device tests and 512-chip dry-runs unchanged.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rule table: single-pod ("data", "model") and multi-pod
# ("pod", "data", "model") meshes share it — "pod" only ever carries batch.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    # activations
    "batch": ("pod", "data"),
    "seq": (),               # sequence usually replicated; SP overrides per-config
    "act_embed": (),
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "act_exp": ("model",),
    "act_vocab": ("model",),
    # params
    "vocab": ("model",),
    "embed": ("data",),      # FSDP / ZeRO-3: weight d_model dim over data axis
    "mlp": ("model",),       # tensor parallel: d_ff over model axis
    "heads": ("model",),
    "kv_heads": ("model",),
    "qkv": ("model",),       # flattened (heads*head_dim) projections
    "experts": ("model",),   # expert parallelism
    "mlp_zero": ("data",),   # ZeRO storage of expert w_down's d_ff dim
    "inner": ("model",),     # SSM inner/expanded dim
    "layers": (),            # stacked-scan layer axis: never sharded
    "state": (),
    # KV cache
    "cache_batch": ("data",),
    "cache_seq": (),
    "cache_heads": ("model",),
}

_local = threading.local()

# Logical axes where uneven (padded) sharding beats replication.
UNEVEN_OK = {"act_heads"}


def current_rules() -> Dict[str, Tuple[str, ...]]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: Optional[Dict[str, Tuple[str, ...]]] = None, **overrides):
    base = dict(rules if rules is not None else DEFAULT_RULES)
    base.update(overrides)
    prev = getattr(_local, "rules", None)
    _local.rules = base
    try:
        yield base
    finally:
        if prev is None:
            del _local.rules
        else:
            _local.rules = prev


def _active_mesh() -> Optional[Mesh]:
    # The mesh of an enclosing ``with mesh:`` block, if any.
    from jax._src import mesh as mesh_lib

    env_mesh = mesh_lib.thread_resources.env.physical_mesh
    return None if env_mesh.empty else env_mesh


def resolve_spec(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
                 mesh: Mesh) -> P:
    """logical axes -> PartitionSpec, dropping non-divisible mappings."""
    rules = current_rules()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    parts = []
    for dim, name in zip(shape, logical_axes):
        if name is None:
            parts.append(None)
            continue
        mesh_axes = [a for a in rules.get(name, ()) if a in axis_sizes and a not in used]
        total = int(np.prod([axis_sizes[a] for a in mesh_axes])) if mesh_axes else 1
        # Activations tolerate UNEVEN sharding (GSPMD pads): e.g. hymba's 25
        # heads on a 16-way axis — replication would redundantly compute the
        # full attention on every model shard (§Perf H7).
        if name in UNEVEN_OK and mesh_axes and dim >= total:
            used.update(mesh_axes)
            parts.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
            continue
        if mesh_axes and dim % total == 0:
            used.update(mesh_axes)
            parts.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
        else:
            # try progressively shorter prefixes (e.g. batch too small for pod*data)
            ok = None
            for cut in range(len(mesh_axes) - 1, 0, -1):
                sub = mesh_axes[:cut]
                tot = int(np.prod([axis_sizes[a] for a in sub]))
                if dim % tot == 0:
                    ok = sub
                    break
            if ok:
                used.update(ok)
                parts.append(tuple(ok) if len(ok) > 1 else ok[0])
            else:
                parts.append(None)
    return P(*parts)


def shard(x, *logical_axes):
    """Apply a sharding constraint from logical axes (no-op without a mesh)."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, logical_axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, shape: Sequence[int],
                   logical_axes: Sequence[Optional[str]]) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(shape, logical_axes, mesh))


def tree_shardings(mesh: Mesh, tree_sds, tree_axes):
    """Map a ShapeDtypeStruct tree + matching logical-axes tree to
    NamedShardings. The SDS tree is primary: its leaves bound the traversal,
    so the axes tuples (which LOOK like containers) are taken whole."""
    return jax.tree_util.tree_map(
        lambda s, ax: named_sharding(mesh, s.shape,
                                     ax if ax is not None else (None,) * len(s.shape)),
        tree_sds, tree_axes)
