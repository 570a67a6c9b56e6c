"""Pipeline parallelism over the mesh 'pipe' axis (GPipe schedule).

No reference equivalent (SURVEY.md §2.3: PP absent; the 'pipe' axis was
reserved as the extension point in round 1 — this fills it in).  TPU-native
design:

- layer parameters are STACKED on a leading num_layers axis and sharded
  over 'pipe' (parallel/sharding.py DEFAULT_PP_RULES), so each pipe rank
  holds only its stage's weights — the memory win of pipeline placement;
- the schedule is the classic GPipe ring: ``n_micro + P - 1`` ticks, each
  tick running one stage forward on every rank and rotating activations to
  the next rank via ``ppermute`` over ICI.  Warmup/drain bubbles compute on
  don't-care activations whose results are never written;
- backward is pure autodiff: ``lax.scan`` + ``ppermute`` transpose to the
  reverse schedule automatically, so there is no hand-written backward
  pipeline to maintain.

Efficiency: bubble fraction is (P-1)/(n_micro+P-1) — pick n_micro >= 4*P
for >80% utilization.  Each rank's per-tick compute is a full MXU-blocked
stage, so the pipeline composes with tensor/data/sequence sharding on the
other mesh axes.
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import DATA_AXIS, PIPE_AXIS, get_global_mesh


def plan_schedule(stages: int, batch: int, requested_micro: int,
                  pipe_axis: str = PIPE_AXIS, data_axis: str = DATA_AXIS):
    """Resolve the shared GPipe invocation decisions for a pipelined stack:
    the global mesh (asserting its pipe axis matches ``stages``), the
    microbatch count (degraded to the largest divisor of ``batch`` for tail
    batches — worse bubble, still exact, one cached recompile per odd
    shape), and the microbatch PartitionSpec (batch dim rides 'data' only
    when it divides evenly; otherwise replicated).

    One implementation for every pipelined stack (transformer_encoder,
    transformer_encoder_with_pair, evoformer) so schedule fixes land once.

    Returns (mesh, n_micro, mb, mb_spec)."""
    mesh = get_global_mesh()
    assert mesh is not None and mesh.shape[pipe_axis] == stages, (
        f"pipeline_stages={stages} needs a global mesh with a matching "
        f"'{pipe_axis}' axis (got "
        f"{None if mesh is None else dict(mesh.shape)})"
    )
    n_micro = max(1, min(requested_micro, batch))
    while batch % n_micro:
        n_micro -= 1
    mb = batch // n_micro
    mb_spec = (
        P(None, data_axis)
        if data_axis in mesh.shape and mb % mesh.shape[data_axis] == 0
        else P()
    )
    return mesh, n_micro, mb, mb_spec


def gpipe(
    mesh,
    stage_apply: Callable[[Any, Any, jnp.ndarray], Any],
    stacked_params,
    microbatches,
    constants,
    rng: Optional[jax.Array] = None,
    pipe_axis: str = PIPE_AXIS,
    mb_spec: P = P(),
    const_specs=None,
    manual_axes=None,
):
    """Run ``stage_apply`` as a GPipe pipeline.

    Args:
        stage_apply: ``(stage_params, mb_tree, rng) -> mb_tree`` — applies
            ONE stage (this rank's slice of the stacked params, leading dim
            num_layers/P) to one microbatch tree; pure.
        stacked_params: pytree with leading dim num_layers on every leaf,
            laid out P('pipe') (each rank receives its stage slice).
        microbatches: pytree with leading dims (n_micro, mb, ...) —
            replicated across the pipe axis.
        constants: pytree of per-call constants (e.g. the attention bias),
            replicated; passed to ``stage_apply`` via closure would break
            shard_map's spec accounting, so they ride as an argument.
        rng: optional base dropout key; folded per (rank, tick) inside.
        mb_spec: PartitionSpec for every microbatch leaf — e.g.
            ``P(None, 'data')`` keeps the batch dim sharded over the data
            axis so the pipeline composes with data parallelism instead of
            all-gathering the batch.
        const_specs: optional pytree of PartitionSpecs matching
            ``constants`` (default: all replicated) — e.g. the stationary
            rel-pos bias sharded by query rows over 'seq' when the stage
            body runs ring attention (dp x pp x sp composition).
        manual_axes: mesh axis names the shard_map runs MANUAL over
            (default: all of them).  Passing e.g. every axis except 'seq'
            leaves 'seq' AUTO: GSPMD keeps partitioning the stage body
            over it, so row-sharded streams (evoformer/unimol) compose
            with the pipeline by re-pinning their sharding constraints
            INSIDE ``stage_apply`` (bare PartitionSpecs — the body's
            context mesh has the manual axes marked) instead of needing
            per-leaf microbatch specs.  ``mb_spec``/``const_specs`` may
            then only mention manual axes.

    Returns the pipeline output microbatches, same structure/shape as
    ``microbatches``, replicated over the pipe axis.
    """
    n_pipe = mesh.shape[pipe_axis]
    n_micro = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    assert n_micro >= 1
    perm = [(i, (i + 1) % n_pipe) for i in range(n_pipe)]
    has_rng = rng is not None

    def local(params_local, mbs, consts, *maybe_rng):
        r = jax.lax.axis_index(pipe_axis)
        base_rng = maybe_rng[0] if has_rng else None
        ticks = n_micro + n_pipe - 1

        mb0 = jax.tree_util.tree_map(lambda a: a[0], mbs)
        zeros_mb = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a), mb0
        )
        outs0 = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), mbs)
        if manual_axes is not None:
            # partial-manual: the scan carries BECOME pipe-varying after
            # one tick (r is pipe-varying), so the initial values must be
            # cast to match the carry type
            mark = lambda a: jax.lax.pcast(a, (pipe_axis,), to="varying")
            zeros_mb = jax.tree_util.tree_map(mark, zeros_mb)
            outs0 = jax.tree_util.tree_map(mark, outs0)

        def tick(carry, t):
            buf, outs = carry
            # rank 0 injects microbatch t during the fill phase; everyone
            # else consumes what the previous rank sent last tick
            inject = jax.tree_util.tree_map(
                lambda a: a[jnp.minimum(t, n_micro - 1)], mbs
            )
            x_in = jax.tree_util.tree_map(
                lambda i, b: jnp.where(r == 0, i, b), inject, buf
            )
            step_rng = None
            if has_rng:
                step_rng = jax.random.fold_in(
                    jax.random.fold_in(base_rng, t), r
                )
            y = stage_apply(params_local, (x_in, consts), step_rng)
            # the LAST rank finished microbatch (t - P + 1) this tick
            done = t - (n_pipe - 1)
            valid = (r == n_pipe - 1) & (done >= 0)
            slot = jnp.clip(done, 0, n_micro - 1)

            def write(o, y_leaf):
                cur = jax.lax.dynamic_index_in_dim(o, slot, keepdims=False)
                new = jnp.where(valid, y_leaf, cur)
                return jax.lax.dynamic_update_index_in_dim(o, new, slot, 0)

            outs = jax.tree_util.tree_map(write, outs, y)
            y_next = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, pipe_axis, perm), y
            )
            return (y_next, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (zeros_mb, outs0), jnp.arange(ticks, dtype=jnp.int32)
        )
        # outputs live on the last rank only; replicate them over the pipe
        # axis (zero elsewhere -> psum = broadcast)
        outs = jax.tree_util.tree_map(
            lambda o: jax.lax.psum(
                jnp.where(r == n_pipe - 1, o, jnp.zeros_like(o)), pipe_axis
            ),
            outs,
        )
        return outs

    pspec = jax.tree_util.tree_map(
        lambda leaf: P(pipe_axis), stacked_params
    )
    in_specs = [
        pspec,
        jax.tree_util.tree_map(lambda _: mb_spec, microbatches),
        (
            const_specs
            if const_specs is not None
            else jax.tree_util.tree_map(lambda _: P(), constants)
        ),
    ]
    operands = [stacked_params, microbatches, constants]
    if has_rng:
        in_specs.append(P())
        operands.append(rng)

    from unicore_tpu.parallel.compat import shard_map

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=jax.tree_util.tree_map(lambda _: mb_spec, microbatches),
        # partial-manual REQUIRES vma checking — the eager path's unmatch
        # step otherwise builds an all-axes spec that mentions the auto
        # axes and is rejected; full-manual keeps it off (the stage body
        # may contain pallas_call, whose out_shapes carry no vma
        # annotation).
        manual_axes=manual_axes,
        check_vma=manual_axes is not None,
    )
    return fn(*operands)
