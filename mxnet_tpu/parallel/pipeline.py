"""Pipeline parallelism: GPipe-style microbatched stage execution over a
``pp`` mesh axis.

The reference has NO pipeline parallelism (SURVEY.md §2.3 marks it
ABSENT — its engine merely overlaps independent graph branches), so
this is a new TPU-native capability beside ring attention: the model's
layers split into S stages, each stage's parameters live on one slice
of the ``pp`` mesh axis, and microbatches stream through the stages
with ``jax.lax.ppermute`` moving activations stage-to-stage over ICI.

Schedule: the classic GPipe loop — with S stages and M microbatches,
one jitted step runs S+M-1 ticks; on each tick every stage computes its
current microbatch (device-parallel across the ``pp`` axis) and the
activations rotate one hop. Bubble fraction = (S-1)/(S+M-1), amortized
by choosing M >> S. Backward rides jax.grad straight through the
``ppermute``s (its transpose is the reverse rotation), so one
``value_and_grad`` of the scheduled forward IS pipelined backward —
no hand-written 1F1B needed for correctness.

All stages must share one layer signature (the classic homogeneous-
stack assumption); embed/head layers live outside the pipelined trunk.

Works like the rest of the parallel package: pure jax + shard_map,
validated on a virtual CPU mesh (tests/test_pipeline.py), composes
with a ``dp`` axis for data parallelism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(stage_params):
    """Stack a list of S per-stage parameter pytrees into one pytree
    whose leaves carry a leading stage axis (to shard over ``pp``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)


def pipeline_apply(stage_fn, stacked_params, x, mesh, n_microbatches,
                   axis="pp", batch_axis=None):
    """Run ``x`` through S pipeline stages.

    stage_fn(params, x) -> y   — one stage's computation; every stage
        uses the same signature/shapes (homogeneous stack).
    stacked_params — pytree with leading stage axis S == mesh.shape[axis]
        (see stack_stage_params); sharded so stage i's slice lives on
        pp-coordinate i.
    x — (B, ...) global batch; split into ``n_microbatches`` along
        axis 0, streamed through the stages, reassembled to (B, ...).

    Differentiable end-to-end: wrap in jax.value_and_grad for pipelined
    training. Compose with data parallelism by passing ``batch_axis``.
    """
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    M = int(n_microbatches)
    if M < 1:
        raise ValueError("n_microbatches must be >= 1")
    n_stages = {leaf.shape[0] for leaf in jax.tree.leaves(stacked_params)}
    if n_stages != {S}:
        raise ValueError(
            "stacked_params lead with %s stages but mesh axis '%s' has "
            "%d devices — they must match (one stage per pp coordinate); "
            "a multiple would silently drop stages" % (
                sorted(n_stages), axis, S))
    B = x.shape[0]
    local_b = B // mesh.shape[batch_axis] if batch_axis else B
    if B % (mesh.shape[batch_axis] if batch_axis else 1) or local_b % M:
        raise ValueError(
            "per-shard batch %d (global %d over %d-way '%s') not "
            "divisible by %d microbatches"
            % (local_b, B, mesh.shape[batch_axis] if batch_axis else 1,
               batch_axis, M))

    param_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    x_spec = P(batch_axis)
    out_spec = P(batch_axis)

    def local(params, xl):
        # params: stage-local pytree (leading axis 1 slice, squeezed)
        params = jax.tree.map(lambda p: p[0], params)
        rank = lax.axis_index(axis)
        micro = xl.reshape((M, xl.shape[0] // M) + xl.shape[1:])
        mshape = micro.shape[1:]

        # tick t: stage s computes microbatch (t - s) if 0 <= t-s < M.
        # `cur` holds the activation entering this stage this tick;
        # outputs collect at the LAST stage, which writes tick t-S+1's
        # result into slot t-S+1.
        nticks = S + M - 1
        outs0 = jnp.zeros((M,) + mshape, xl.dtype)
        cur0 = jnp.zeros(mshape, xl.dtype)
        # constants start device-invariant; mark them varying over every
        # sharded axis so the scan carry types line up (shard_map vma)
        vary_axes = tuple(a for a in (batch_axis, axis) if a)
        cur0, outs0 = (lax.pcast(v, vary_axes, to="varying")
                       for v in (cur0, outs0))
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            cur, outs = carry
            # stage 0 ingests microbatch t (clamped; masked below)
            feed = micro[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(rank == 0, feed, cur)
            live = jnp.logical_and(t - rank >= 0, t - rank < M)
            y = stage_fn(params, cur)
            y = jnp.where(live, y, cur)
            # last stage banks its finished microbatch (t - S + 1)
            slot = jnp.clip(t - S + 1, 0, M - 1)
            bank = jnp.logical_and(rank == S - 1, t - (S - 1) >= 0)
            outs = jnp.where(
                bank,
                lax.dynamic_update_index_in_dim(outs, y, slot, 0),
                outs)
            # rotate activations one hop down the pipe
            cur = lax.ppermute(y, axis, perm)
            return (cur, outs), None

        (cur, outs), _ = lax.scan(tick, (cur0, outs0),
                                  jnp.arange(nticks))
        # results were banked only on the last stage (others hold
        # zeros): one psum replicates them to every pp coordinate
        outs = lax.psum(outs, axis)
        return outs.reshape((M * mshape[0],) + mshape[1:])

    in_specs = (param_spec, x_spec)
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                      out_specs=out_spec)
    return fn(stacked_params, x)
