"""Logical-axis sharding rules: GSPMD parameter/activation placement.

The reference has no in-tree tensor/model parallelism (SURVEY.md §2.4 — TP/PP
are delegated to DeepSpeed/vLLM integrations); on TPU this is the natural
first-class citizen.  Arrays carry *logical* axis names ("batch", "embed",
"heads", ...), and a rule table maps logical names to mesh axes ("data",
"fsdp", "tensor", ...).  jit + NamedSharding then compiles the collectives.

This mirrors the flax/t5x logical-axis-rules idiom, rebuilt standalone so the
framework does not depend on flax internals.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

Rules = Sequence[Tuple[str, Union[str, Tuple[str, ...], None]]]

# Default rule table for transformer training: FSDP over params' embed axis,
# tensor parallel over heads/mlp, sequence parallel over tokens, expert
# parallel over the expert axis.
DEFAULT_RULES: Rules = (
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
    ("embed", "fsdp"),
    ("heads", "tensor"),
    ("kv", None),
    ("head_dim", None),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    # State-space layers (models/hybrid.py): the inner width shards like
    # the feed-forward's; a channel's state and conv taps stay together.
    ("ssm_inner", "tensor"),
    ("ssm_state", None),
    ("ssm_conv", None),
    # Layer dim shards over the stage axis: with stage>1 each device
    # holds its pipeline stage's contiguous run of layers at rest, so
    # the [L,...] -> [S, L/S, ...] regroup in the pipelined forward is a
    # local reshape (no resharding).  Size-1 stage axes make this a
    # no-op.
    ("layers", "stage"),
)


def spec_from_logical(logical_axes: Sequence[Optional[str]],
                      rules: Rules = DEFAULT_RULES,
                      mesh=None):
    """Map logical axis names to a `PartitionSpec` via the rule table.

    A mesh axis is used at most once per spec (first logical axis wins),
    matching GSPMD's constraint that a mesh axis shards one array dim.
    Axes whose mesh axis does not exist in `mesh` (or maps to None) are
    replicated.
    """
    from jax.sharding import PartitionSpec

    table = dict(rules)
    mesh_axes = set(mesh.axis_names) if mesh is not None else None
    used: set = set()
    out: List[Union[str, Tuple[str, ...], None]] = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        target = table.get(name)
        if target is None:
            out.append(None)
            continue
        targets = target if isinstance(target, tuple) else (target,)
        picked = tuple(
            t for t in targets
            if t not in used and (mesh_axes is None or t in mesh_axes))
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(picked)
    return PartitionSpec(*out)


def named_sharding(mesh, logical_axes: Sequence[Optional[str]],
                   rules: Rules = DEFAULT_RULES):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, spec_from_logical(logical_axes, rules, mesh))


def with_logical_constraint(x, logical_axes: Sequence[Optional[str]],
                            rules: Rules = DEFAULT_RULES, mesh=None):
    """`lax.with_sharding_constraint` by logical names (inside jit)."""
    import jax

    if mesh is None:
        env_mesh = jax.sharding.get_abstract_mesh()
        if env_mesh is None or env_mesh.empty:
            return x
        mesh = env_mesh
    from jax.sharding import NamedSharding

    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_from_logical(
                logical_axes, rules, mesh)))
    except (TypeError, ValueError):
        # AbstractMesh from an ambient context: constrain by spec.
        return jax.lax.with_sharding_constraint(
            x, spec_from_logical(logical_axes, rules, mesh))


def tree_shardings(mesh, logical_tree: Any, rules: Rules = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    `logical_tree` leaves are tuples/lists of logical axis names (or None),
    typically produced by `infer_logical_axes` or stored next to params.
    """
    import jax

    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda v: isinstance(v, (tuple, list)) and (
            not v or v[0] is None or isinstance(v[0], str)),
    )


def infer_logical_axes(params: Any,
                       table: Optional[Dict[str, Sequence[str]]] = None):
    """Heuristic logical axes for a param pytree keyed by path names.

    Used when a model does not annotate its params: embedding/vocab matrices
    shard on vocab, attention projections on heads/embed, MLP on mlp/embed.
    Works for the in-tree models (models/transformer.py names its params to
    match).  Leaves default to fsdp-on-largest-axis.
    """
    import jax
    import numpy as np

    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def infer_one(path, leaf) -> Tuple[Optional[str], ...]:
        keys = "/".join(
            getattr(p, "key", getattr(p, "name", str(getattr(p, "idx", ""))))
            for p in path).lower()
        nd = np.ndim(leaf)
        if nd == 0:
            return ()
        if nd == 1:
            return (None,)
        if "embed" in keys and ("tok" in keys or "vocab" in keys or
                                "wte" in keys):
            return ("vocab", "embed") + (None,) * (nd - 2)
        if any(k in keys for k in ("wq", "wk", "wv", "q_proj", "k_proj",
                                   "v_proj", "query", "key", "value")):
            return ("embed", "heads") + (None,) * (nd - 2)
        if any(k in keys for k in ("wo", "o_proj", "out_proj", "attn_out")):
            return ("heads", "embed") + (None,) * (nd - 2)
        if any(k in keys for k in ("w_up", "up_proj", "gate", "w_gate", "wi",
                                   "fc1")):
            return ("embed", "mlp") + (None,) * (nd - 2)
        if any(k in keys for k in ("w_down", "down_proj", "wo_mlp", "fc2")):
            return ("mlp", "embed") + (None,) * (nd - 2)
        if "lm_head" in keys or "output" in keys:
            return ("embed", "vocab") + (None,) * (nd - 2)
        # default: shard the largest dim on fsdp
        shape = np.shape(leaf)
        big = int(np.argmax(shape))
        return tuple("embed" if i == big else None for i in range(nd))

    leaves = [infer_one(path, leaf) for path, leaf in flat]
    treedef = jax.tree_util.tree_structure(params)
    # scan-stacked layers: leading 'layers' axis handled by caller
    return jax.tree_util.tree_unflatten(treedef, leaves)


def shard_tree(params: Any, mesh, rules: Rules = DEFAULT_RULES,
               logical_tree: Any = None):
    """Device-put a param pytree with inferred or provided logical axes."""
    import jax

    if logical_tree is None:
        logical_tree = infer_logical_axes(params)
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.device_put(params, shardings)


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def data_sharding(mesh, batch_axes: Sequence[str] = ("data", "fsdp")):
    """Sharding for a host batch: leading dim over the data(+fsdp) axes."""
    from jax.sharding import NamedSharding, PartitionSpec

    axes = tuple(a for a in batch_axes if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    if not axes:
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(
        mesh, PartitionSpec(axes if len(axes) > 1 else axes[0]))
