"""Logical-axis sharding policy (MaxText-style axis rules).

Every parameter leaf carries a tuple of logical axis names (assigned at
init, see repro.models.*).  A *policy* maps logical names to mesh axes;
``build_specs`` turns (shapes, axes, policy, mesh) into PartitionSpecs
with two safety rules applied left-to-right per leaf:

  * divisibility — a mesh axis is only assigned if it divides the dim
    (this is what routes grok-1's 8 experts to d_ff TP while qwen3-moe's
    128 experts get true expert parallelism, with no per-arch code);
  * uniqueness  — a mesh axis is used at most once per leaf.

Policies:
  * ``tp``       — tensor parallelism on "model"; params replicated over
    the data axes (small models);
  * ``fsdp``     — tp + remaining dims sharded over ("pod","data")
    (fully-sharded params for big models);
  * optimizer states always use the fsdp rules (ZeRO-1): m/v are sharded
    over data even when params are tp-replicated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["rules_for", "build_specs", "param_policy", "batch_spec",
           "cache_specs", "named", "FSDP_THRESHOLD"]

# parameters above this count get fully-sharded (fsdp) treatment
FSDP_THRESHOLD = 15e9

MeshAxes = Tuple[str, ...]


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def rules_for(policy: str, mesh: Mesh) -> Dict[str, Any]:
    dp = _dp_axes(mesh)
    model = "model"
    if policy == "serve2d":
        # Serving layout for models too big for plain TP: weight matrices
        # shard over (data x model) JOINTLY and stay resident — no
        # per-layer parameter all-gather on the decode path (grads/opt
        # don't exist when serving, so "data" is free for weights; the
        # tiny per-token activations get gathered instead).  §Perf.
        model = tuple(dp) + ("model",)
    rules: Dict[str, Any] = {
        "vocab": model,
        "q_proj": model,
        "kv_proj": model,
        "mlp": model,
        "expert": model,
        "lru": model,
        "ssm_in": model,
        "ssm_inner": model,
        "ssm_conv": model,
        "embed": dp if policy == "fsdp" else None,
        "head_dim": None,
        "ssm_heads": None,
        "layers": None,       # scan axis stays unsharded
    }
    return rules


def param_policy(cfg) -> str:
    return "fsdp" if cfg.param_count() > FSDP_THRESHOLD else "tp"


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        return int(np.prod([mesh.shape[a] for a in ax]))
    return mesh.shape[ax]


def _spec_for_leaf(shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
                   rules: Dict[str, Any], mesh: Mesh) -> P:
    assert len(shape) == len(axes), (shape, axes)
    used: set = set()
    out = []
    for dim, name in zip(shape, axes):
        ax = rules.get(name) if name is not None else None
        flat = tuple(ax) if isinstance(ax, tuple) else ((ax,) if ax else ())
        if (ax is not None and not (set(flat) & used)
                and dim % _axis_size(mesh, ax) == 0 and dim > 0):
            out.append(ax)
            used.update(flat)
        else:
            out.append(None)
    return P(*out)


def build_specs(shapes, axes, policy: str, mesh: Mesh):
    """shapes/axes: matching pytrees (ShapeDtypeStructs + logical tuples).
    Returns a pytree of PartitionSpecs."""
    rules = rules_for(policy, mesh)
    return _tree_specs(shapes, axes, rules, mesh)


def _tree_specs(shapes, axes, rules, mesh):
    # axes leaves are tuples-of-strings; walk the two trees together with
    # the axes tree's structure defining the leaves.
    flat_axes, treedef = jax.tree.flatten(
        axes, is_leaf=lambda t: isinstance(t, tuple)
        and all(isinstance(x, (str, type(None))) for x in t))
    flat_shapes = treedef.flatten_up_to(shapes)
    specs = [_spec_for_leaf(s.shape, a, rules, mesh)
             for s, a in zip(flat_shapes, flat_axes)]
    return jax.tree.unflatten(treedef, specs)


def batch_spec(mesh: Mesh, ndim: int, batch_divisible: bool = True) -> P:
    """Batch-leading activations: shard batch over (pod, data) when the
    global batch divides; everything else replicated."""
    dp = _dp_axes(mesh)
    lead = dp if (batch_divisible and dp) else None
    return P(lead, *([None] * (ndim - 1)))


def cache_specs(cfg, mesh: Mesh, batch: int, seq: int):
    """PartitionSpec factory for serving caches.

    attention (B, S, KV, D): batch over dp when divisible; KV heads over
    "model" when divisible, else the sequence axis takes "model" (context
    sharding) — the policy that keeps 32k caches inside HBM for GQA archs
    whose few KV heads don't divide the model axis."""
    dp = _dp_axes(mesh)
    dp_ok = batch % _axis_size(mesh, dp) == 0 if dp else False
    b_ax = dp if dp_ok else None
    m = mesh.shape["model"]

    def attn(kv_heads: int, cache_len: int) -> P:
        if kv_heads % m == 0:
            return P(b_ax, None, "model", None)
        if cache_len % m == 0:
            return P(b_ax, "model", None, None)
        return P(b_ax, None, None, None)

    return dict(
        attn=attn,
        conv=lambda c: P(b_ax, None, "model" if c % m == 0 else None),
        lru_h=lambda w: P(b_ax, "model" if w % m == 0 else None),
        ssm_h=lambda h: P(b_ax, "model" if h % m == 0 else None, None, None),
        batch_axis=b_ax,
    )


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda t: isinstance(t, P))


def reshard_tree(tree, axes, policy: str = "tp"):
    """Best-effort re-shard of a param tree to ``policy`` rules under the
    mesh installed by ``jax.set_mesh`` (no-op without one).  Used to
    hoist FSDP->TP parameter all-gathers to once-per-step instead of
    once-per-microbatch: the forward/backward consume the TP view while
    optimizer state stays fully sharded (§Perf)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return tree
    rules = rules_for(policy, mesh)
    specs = _tree_specs(tree, axes, rules, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs)


def constrain(x, *spec):
    """Best-effort ``with_sharding_constraint``: applied only when a mesh
    with the named axes is active and every constrained dim divides.

    Model code calls this at sharding-critical intermediates (e.g. MoE
    dispatch buffers) so the SPMD partitioner keeps them distributed
    instead of falling back to replicate+all-reduce.  The mesh is the one
    installed by ``jax.set_mesh``; without one it is a no-op, keeping
    smoke tests mesh-free."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    out = []
    for dim, ax in zip(x.shape, spec):
        flat = () if ax is None else ((ax,) if isinstance(ax, str)
                                      else tuple(ax))
        flat = tuple(a for a in flat if a in names)   # drop absent axes
        if flat:
            size = int(np.prod([mesh.shape[a] for a in flat]))
            if dim % size == 0 and dim > 0:
                out.append(flat[0] if len(flat) == 1 else flat)
                continue
        out.append(None)
    if all(o is None for o in out):
        return x
    return jax.lax.with_sharding_constraint(x, P(*out))
