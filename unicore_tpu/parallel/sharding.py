"""Parameter-sharding rules (pjit partition specs).

Replaces the reference's DDP wrapper selection
(/root/reference/unicore/models/distributed_unicore_model.py:37-63) — on TPU
there is no wrapper: state lives as sharded jax.Arrays and XLA inserts the
collectives.  ``--ddp-backend`` maps to a preset:

    c10d / apex / no_c10d / legacy_ddp -> 'replicated' (pure DP, grads psum'd)
    + --zero-shard-optimizer           -> fp32 master/opt state sharded over
                                          'data' (ZeRO-1)
    + --model-parallel-size > 1        -> 2D megatron-style tensor sharding
                                          by param-name rules
"""

import logging
import re
from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    dp_axis_names,
    dp_world_size,
)

logger = logging.getLogger(__name__)


# Megatron-style rules: column-parallel for up-projections / qkv, row-parallel
# for down-projections.  Matched against the '/'-joined param path.
DEFAULT_TP_RULES = [
    # attention qkv / in_proj: shard output features
    (r".*(q_proj|k_proj|v_proj|in_proj|qkv).*kernel", P(None, MODEL_AXIS)),
    (r".*(q_proj|k_proj|v_proj|in_proj|qkv).*bias", P(MODEL_AXIS)),
    # attention output projection: shard input features
    (r".*(out_proj|o_proj).*kernel", P(MODEL_AXIS, None)),
    # MLP up: shard output features
    (r".*(fc1|up_proj|gate_proj|wi).*kernel", P(None, MODEL_AXIS)),
    (r".*(fc1|up_proj|gate_proj|wi).*bias", P(MODEL_AXIS)),
    # MLP down: shard input features
    (r".*(fc2|down_proj|wo).*kernel", P(MODEL_AXIS, None)),
    # embeddings: shard vocab dim
    (r".*embed_tokens.*embedding", P(MODEL_AXIS, None)),
]

# Expert-parallel rules: MoE expert weights carry a leading num_experts dim
# (modules/moe.py) sharded over the 'expert' mesh axis; XLA emits the token
# all-to-alls from these annotations.
DEFAULT_EP_RULES = [
    (r".*experts_fc(1|2)", P(EXPERT_AXIS, None, None)),
    (r".*experts_bias(1|2)", P(EXPERT_AXIS, None)),
]

# Pipeline-parallel rules: stacked per-layer params (leading num_layers dim,
# modules/transformer_encoder.py pipeline_stack) shard over 'pipe' so each
# rank holds only its stage's weights.
DEFAULT_PP_RULES = [
    (r".*pipeline_stack.*", P(PIPE_AXIS)),
]


#: ``--ddp-backend`` choices, all mapping to the same XLA-SPMD base preset
#: (module docstring above): state lives as sharded jax.Arrays and XLA
#: emits the gradient psums — there is no wrapper to pick.
DDP_BACKEND_CHOICES = ("c10d", "apex", "no_c10d", "legacy_ddp")


_zero_shim_warned = False


def resolve_zero_stage(args) -> int:
    """ZeRO stage from the flags, honoring the deprecation shim:
    ``--zero-shard-optimizer`` (the old boolean) means ``--zero-stage 1``
    and warns once.  An explicit ``--zero-stage`` wins when both are set
    (the boolean then adds nothing)."""
    global _zero_shim_warned
    stage = int(getattr(args, "zero_stage", 0) or 0)
    if getattr(args, "zero_shard_optimizer", False):
        if not _zero_shim_warned:
            _zero_shim_warned = True
            logger.warning(
                "--zero-shard-optimizer is deprecated; use --zero-stage 1 "
                "(stages 2/3 additionally shard the flat gradient / master "
                "buffers — docs/performance.md, 'Memory headroom')"
            )
        stage = max(stage, 1)
    if stage >= 2 and not getattr(args, "fused_adam", False):
        raise ValueError(
            f"--zero-stage {stage} shards the fused optimizer's flat "
            "buffers and therefore requires --fused-adam (stages 2/3 have "
            "no per-leaf equivalent; use --zero-stage 1 for the per-leaf "
            "sharding)"
        )
    return stage


def resolve_ddp_preset(args) -> str:
    """The sharding preset ``--ddp-backend`` (+ modifier flags) selects.

    Every torch backend choice maps to the same replicated-DP base on TPU
    (grads psum'd by XLA); ``--zero-shard-optimizer`` layers ZeRO-1
    master/optimizer-state sharding on top and ``--model-parallel-size``
    layers 2D megatron-style tensor sharding.  Returns the preset name
    (``"replicated"``, ``"zero1"``, ``"tensor_parallel"`` or
    ``"zero1+tensor_parallel"``) and logs the resolution once so operators
    see what their torch-era flags actually did.
    """
    backend = getattr(args, "ddp_backend", "c10d")
    if backend not in DDP_BACKEND_CHOICES:
        raise ValueError(
            f"unknown --ddp-backend {backend!r} "
            f"(choices: {', '.join(DDP_BACKEND_CHOICES)})"
        )
    layers = []
    stage = resolve_zero_stage(args)
    if stage > 0:
        layers.append(f"zero{stage}")
    if getattr(args, "model_parallel_size", 1) > 1:
        layers.append("tensor_parallel")
    preset = "+".join(layers) if layers else "replicated"
    logger.info(
        f"--ddp-backend={backend} -> XLA SPMD preset '{preset}' "
        "(no DDP wrapper on TPU; XLA inserts the gradient collectives)"
    )
    return preset


def param_spec(path: str, shape, rules=None, axis_sizes=None) -> P:
    """Partition spec for one parameter by path-rule matching.

    ``axis_sizes``: mesh axis-name -> size; a rule only applies when every
    sharded dim is divisible by its axis size (otherwise replicate)."""
    rules = DEFAULT_TP_RULES if rules is None else rules
    for pattern, spec in rules:
        if re.fullmatch(pattern, path):
            if len(spec) > len(shape):
                return P()
            if axis_sizes is not None:
                for dim, entry in enumerate(spec):
                    if entry is None:
                        continue
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    size = 1
                    for axis in axes:
                        if axis not in axis_sizes:
                            return P()  # unknown mesh axis: replicate
                        size *= axis_sizes[axis]
                    if shape[dim] % size != 0:
                        return P()  # indivisible: replicate (no fall-through)
            return spec
    return P()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def params_pspecs(params, use_tp: bool = False, rules=None, mesh: Mesh = None):
    """PartitionSpec pytree for a parameter pytree.

    Pure DP: everything replicated.  With ``use_tp``, apply the megatron
    rules.  The result feeds jit in/out shardings; gradient psums over the
    data axis are then emitted by XLA automatically.
    """
    axis_sizes = dict(mesh.shape) if mesh is not None else None
    use_ep = mesh is not None and mesh.shape.get(EXPERT_AXIS, 1) > 1
    use_pp = mesh is not None and mesh.shape.get(PIPE_AXIS, 1) > 1

    def spec_for(path, leaf):
        p = _path_str(path)
        if use_pp:
            s = param_spec(p, leaf.shape, DEFAULT_PP_RULES, axis_sizes)
            if s != P():
                return s
        if use_ep:
            s = param_spec(p, leaf.shape, DEFAULT_EP_RULES, axis_sizes)
            if s != P():
                return s
        if not use_tp:
            return P()
        return param_spec(p, leaf.shape, rules, axis_sizes)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def zero1_pspecs(params, mesh: Mesh):
    """ZeRO-1: shard fp32 master params / optimizer moments over the
    data-parallel tier (both dp axes when the plan declares a DCN tier)
    along each leaf's largest divisible dim (optional capability beyond
    the reference, SURVEY.md §2.3)."""
    ndata = dp_world_size(mesh)
    dp_axes = dp_axis_names(mesh)

    def spec_for(leaf):
        for dim, size in enumerate(leaf.shape):
            if size % ndata == 0 and size >= ndata:
                spec = [None] * leaf.ndim
                spec[dim] = dp_axes
                return P(*spec)
        return P()

    return jax.tree_util.tree_map(spec_for, params)


def named(mesh: Mesh, pspecs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def seq_row_constrainer(seq_len: int, enabled: bool, what: str = "stream"):
    """GSPMD row-sharding helper for models whose attention outputs are
    themselves model outputs (Uni-Mol pair stream, Evoformer msa/pair
    streams) — the ring/ulysses paths can't serve those, so the stream is
    pinned row-sharded over the mesh 'seq' axis and XLA inserts the
    gathers the row-local attention needs.

    Returns ``constrain(t, row_dim)``: dim ``row_dim`` -> 'seq', dim 0 ->
    'data' (when live); an identity when sharding can't engage (disabled,
    no live seq axis, or seq doesn't divide ``seq_len``).  The returned
    function carries ``.engaged`` so callers that must react to the
    decision (e.g. disabling a non-partitionable pallas_call route) read
    it from the SAME predicate instead of re-deriving it."""
    from .mesh import SEQ_AXIS, get_global_mesh, warn_once

    mesh = get_global_mesh()
    n_seq = 1 if mesh is None else mesh.shape.get(SEQ_AXIS, 1)
    if not (enabled and n_seq > 1 and seq_len % n_seq == 0):
        if enabled and n_seq > 1:
            warn_once(
                logging.getLogger(__name__),
                f"{what} seq sharding: seq axis {n_seq} does not divide "
                f"L={seq_len}; running replicated over seq",
            )

        def identity(t, row_dim):
            return t

        identity.engaged = False
        return identity

    data_ax = dp_axis_names(mesh) if dp_world_size(mesh) > 1 else None

    def constrain(t, row_dim):
        spec = [None] * t.ndim
        spec[0] = data_ax
        spec[row_dim] = SEQ_AXIS
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, P(*spec))
        )

    constrain.engaged = True
    return constrain


def seq_pipeline_plan(seq_len: int, enabled: bool, what: str = "stream"):
    """Composition plan for row-sharding a pipelined stack over the mesh
    'seq' axis (dp x pp x sp for the attention-as-output families:
    unimol pair encoder, evoformer).

    The pipeline's shard_map runs MANUAL over every axis EXCEPT 'seq'
    (gpipe ``manual_axes``); 'seq' stays an AUTO (GSPMD) axis, so the same
    row-sharding that serves the non-pipelined stacks keeps working inside
    each stage body — no per-leaf microbatch specs needed.

    Returns ``(pin, pin_inside, manual_axes)``:

    - ``pin(t, row_dim)``: OUTER constraint pinning ``row_dim`` to 'seq'
      (applied to the microbatch-shaped arrays before gpipe, so GSPMD
      carries the layout across the shard_map boundary);
    - ``pin_inside(t, row_dim)``: the same pin for use INSIDE the gpipe
      stage body — a bare PartitionSpec, since the body's context mesh
      marks the manual axes and a concrete-mesh NamedSharding would be
      rejected there;
    - ``manual_axes``: the axis-name set to pass to gpipe.

    Carries ``pin.engaged`` like :func:`seq_row_constrainer`; when the
    sharding can't engage (no live seq axis, or it doesn't divide
    ``seq_len``) both pins are identities and ``manual_axes`` is None
    (full-manual gpipe, replicated over seq — with a one-shot warning,
    matching the non-pipelined helper's behavior)."""
    from .mesh import SEQ_AXIS, get_global_mesh, warn_once

    mesh = get_global_mesh()
    n_seq = 1 if mesh is None else mesh.shape.get(SEQ_AXIS, 1)
    if not (enabled and n_seq > 1 and seq_len % n_seq == 0):
        if enabled and n_seq > 1:
            warn_once(
                logging.getLogger(__name__),
                f"{what} seq sharding: seq axis {n_seq} does not divide "
                f"L={seq_len}; running the pipeline replicated over seq",
            )

        def identity(t, row_dim):
            return t

        identity.engaged = False
        return identity, identity, None

    def pin(t, row_dim):
        spec = [None] * t.ndim
        spec[row_dim] = SEQ_AXIS
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, P(*spec))
        )

    def pin_inside(t, row_dim):
        spec = [None] * t.ndim
        spec[row_dim] = SEQ_AXIS
        return jax.lax.with_sharding_constraint(t, P(*spec))

    pin.engaged = True
    pin_inside.engaged = True
    from unicore_tpu.parallel.compat import manual_axes_except

    manual_axes = manual_axes_except(mesh, SEQ_AXIS)
    return pin, pin_inside, manual_axes
