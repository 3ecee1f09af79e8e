"""Logical-axis sharding rules → PartitionSpecs.

The flax ``logical axis rules`` idea, standalone: model code annotates each
param with logical axis names; one rules table maps those to mesh axes. The
checkpoint engine needs no extra metadata — the resulting NamedShardings
ride on the arrays (SURVEY.md §2.7: ckpt shard layout keyed by mesh axes).
"""

from typing import Dict, Optional, Sequence, Tuple

from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.common.log import log_once

# logical axis name → mesh axis (or None = replicate).
# "batch" spreads over both data axes; "embed" (the hidden dim of params)
# shards over fsdp (ZeRO-3-style); "mlp" over tp. The hidden states
# outside the experts are replicated over ep and tp, so three logical
# axes split over the two together, and a chip computes its share of
# each where it is:
# - "heads"/"kv_heads", attention's query and key/value heads (the
#   output columns of wq, wk, wv, the input rows of wo): a chip projects,
#   attends and un-projects its own heads, and one all-reduce after wo
#   joins them (models/llama.py _attention). Where ep has one device the
#   rule reads tp alone (:func:`rule_for`);
# - "vocab", the columns of the output head and the rows of the
#   embedding: a chip makes the logits of its own columns and the loss
#   is taken over them where they are, never gathered (models/llama.py
#   head_nll);
# - "expert_mlp", the FFN width of an expert leaf: every chip of an ep
#   group holds every expert at a slice of its columns, so the group's
#   work is even whatever the routing (models/moe.py).
# "seq" over sp (ring attention axis); "layers"/"stage" over pp.
DEFAULT_RULES: Dict[str, Optional[object]] = {
    "batch": ("dcn", "dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "heads": ("ep", "tp"),
    "kv_heads": ("ep", "tp"),
    "mlp": "tp",
    "vocab": ("ep", "tp"),
    "expert_mlp": ("ep", "tp"),
    "stage": "pp",
    # depth-stacked layer params live stage-major: the leading layer dim
    # shards over pp so pipeline_apply's shard_map in_spec P("pp") is
    # satisfied by a local reshape + fsdp all-gather instead of XLA's
    # "involuntary full rematerialization" (replicate-then-repartition).
    # On pp=1 meshes the axis has size 1 — a no-op.
    "layers": "pp",
    "norm": None,
    "head_dim": None,
}


# the logical axes whose rule leaves ep out where it has one device: on a
# mesh without an expert group attention is laid out, to the annotation,
# as by a rule that names tp alone, so such a mesh's lowered programs
# (and the compile-cache entries keyed on them) do not change with ep
_EP_WHERE_SPLIT = ("heads", "kv_heads")


def rule_for(name: str, rules: Optional[Dict] = None, mesh=None):
    """The mesh axes that logical axis ``name`` maps to, on ``mesh`` where
    one is given."""
    rule = (rules or DEFAULT_RULES).get(name)
    if (mesh is not None and name in _EP_WHERE_SPLIT
            and isinstance(rule, tuple) and mesh.shape.get("ep", 1) == 1):
        rule = tuple(a for a in rule if a != "ep")
        rule = rule[0] if len(rule) == 1 else (rule or None)
    return rule


def spec_for(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict] = None,
    mesh=None,
) -> P:
    return P(*[
        rule_for(name, rules, mesh) if name is not None else None
        for name in logical_axes
    ])


def sharding_for(mesh, logical_axes: Sequence[Optional[str]],
                 rules: Optional[Dict] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules, mesh))


def tree_shardings(mesh, logical_tree, rules: Optional[Dict] = None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    import jax

    return jax.tree.map(
        lambda axes: sharding_for(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(axis, 1)


def valid_spec_for(mesh, shape, logical_axes: Sequence[Optional[str]],
                   rules: Optional[Dict] = None) -> P:
    """Like :func:`spec_for` but drops (replicates) any mesh axis whose size
    does not divide the corresponding array dimension — e.g. an elastic
    re-mesh landing on fsdp=3 with a dim of 64 replicates that dim instead
    of failing. GSPMD would need padding for uneven shards; replication is
    always-correct and the planner keeps axes power-of-two in practice."""
    spec = clamp_spec(mesh, spec_for(logical_axes, rules, mesh))
    cleaned = []
    for dim, axis in zip(shape, spec):
        size = axis_size(mesh, axis)
        cleaned.append(axis if (size > 1 and dim % size == 0) else
                       (axis if size == 1 else None))
    return P(*cleaned)


def clamp_spec(mesh, spec: P) -> P:
    """Drop axis names the mesh doesn't carry from a PartitionSpec.

    The library-default batch specs name every data axis incl. ``dcn``;
    hand-built meshes (tests, user code with custom axes) may omit some —
    sharding over an absent axis is a no-op anyway, so dropping the name
    is semantics-preserving and keeps shard_map's axis check happy.
    """
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.shape)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry if entry in mesh.shape else None

    return P(*[keep(e) for e in spec])


def vocab_split(mesh, vocab_size: int):
    """(mesh axes, chips) that the ``vocab`` rule spreads a vocabulary of
    this size over on this mesh: ``(None, 1)`` without a mesh, on a mesh
    whose ``vocab`` axes all have size 1, and, said once, where their
    product does not divide the vocabulary (``valid_spec_for`` then keeps
    the head and the embedding whole on every chip)."""
    if mesh is None:
        return None, 1
    over = valid_spec_for(mesh, (vocab_size,), ("vocab",))[0]
    chips, wanted = (axis_size(mesh, axes)
                     for axes in (over, DEFAULT_RULES["vocab"]))
    if chips != wanted:
        log_once(
            "output head: vocabulary %s is not divisible by the %s chips "
            "of mesh axes %s — head, embedding and logits stay whole on "
            "every chip", vocab_size, wanted, DEFAULT_RULES["vocab"],
        )
    return (over, chips) if chips > 1 else (None, 1)


def head_split(mesh, n_heads: int):
    """(mesh axes, chips) that the ``heads`` rule splits ``n_heads``
    attention heads over on this mesh: the rule as :func:`valid_spec_for`
    reads it, named even where its axes all have size 1 (``tp`` on one
    chip); ``(None, 1)``, said once, where their product does not divide
    the heads, which then stay whole on every chip."""
    over = valid_spec_for(mesh, (n_heads,), ("heads",))[0]
    wanted = rule_for("heads", mesh=mesh)
    chips = axis_size(mesh, over)
    if chips != axis_size(mesh, wanted):
        log_once(
            "attention: %s heads are not divisible by the %s chips of mesh "
            "axes %s — every chip computes every head", n_heads,
            axis_size(mesh, wanted), wanted,
        )
    return over, chips


def shard_tree(mesh, state, logical_tree, rules: Optional[Dict] = None):
    """device_put a pytree according to its logical axes (with per-leaf
    divisibility validation)."""
    import jax

    def put(axes, leaf):
        spec = valid_spec_for(mesh, leaf.shape, axes, rules)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    # logical_tree leads: its tuple leaves (marked via is_leaf) pair with
    # the array leaves of ``state`` at the same tree positions
    return jax.tree.map(
        put, logical_tree, state,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x
        ),
    )


def batch_sharding(mesh) -> NamedSharding:
    """Input batch: (batch, seq) over ((dcn, dp, fsdp), sp)."""
    return NamedSharding(mesh, clamp_spec(mesh, P(("dcn", "dp", "fsdp"), "sp")))


def with_batch_constraint(x, mesh=None):
    """Annotate an activation inside jit: batch over data axes, seq over sp.

    Pass ``mesh`` when it may lack some data axes (hand-built meshes) so
    the spec clamps to the axes that exist."""
    import jax

    spec = P(("dcn", "dp", "fsdp"), "sp")
    if mesh is not None:
        spec = clamp_spec(mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)


def global_batch_from_local(mesh, local_batch, spec: Optional[P] = None):
    """Assemble the global input batch from this process's host-local
    shard (the multi-host data path: each host's loader yields
    ``global_batch / num_processes`` rows; the result is one global
    ``jax.Array`` sharded over the data axes, ready for a pjit step).

    The torchrun analogue is DistributedSampler + an implicitly-local
    tensor; jax needs the explicit local→global assembly
    (``jax.make_array_from_process_local_data``). Single-process: plain
    device_put with the same sharding.
    """
    import jax
    import numpy as np

    spec = spec if spec is not None else clamp_spec(
        mesh, P(("dcn", "dp", "fsdp"))
    )
    sharding = NamedSharding(mesh, spec)
    local = np.asarray(local_batch)
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    # let jax derive the global shape from the sharding: the scale factor
    # is how many processes hold DISTINCT batch shards, which is NOT always
    # process_count (model axes spanning hosts — e.g. sp across hosts —
    # make some hosts batch-replicas that must feed identical rows)
    # a genuinely mis-sized feed fails loudly at the next reshape/jit, so
    # no extra guard here — any shard-count heuristic mis-fires on meshes
    # where model axes span hosts (some processes are batch replicas)
    return jax.make_array_from_process_local_data(sharding, local)
