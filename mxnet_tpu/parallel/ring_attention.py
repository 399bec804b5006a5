"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference (MXNet ~1.2) predates attention entirely (SURVEY.md §5.7),
but long-context scaling is first-class in this framework: sequences too
long for one chip's HBM shard across a ``sp`` mesh axis, and attention
runs as either

* **ring attention** (`ring_attention`) — K/V blocks rotate around the
  ring via ``lax.ppermute`` while each device keeps a flash-attention-
  style online softmax (running max + denominator) over its local Q
  shard. Compute overlaps the ICI transfer of the next block; memory per
  chip is O(T/n) with no full-sequence materialization anywhere.
* **Ulysses all-to-all** (`ulysses_attention`) — ``lax.all_to_all``
  re-shards from sequence-split to head-split, runs dense attention on
  full sequences per head group, and re-shards back. Cheaper collective
  volume for moderate T; requires heads % sp == 0.

Both are pure jax (shard_map + collectives), differentiate through the
collectives, and validate on a virtual CPU mesh exactly like the rest of
the multi-chip suite; `attention_reference` is the single-device oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["attention_reference", "ring_attention", "ulysses_attention"]

_NEG_INF = -1e30

def _shard_map():
    """shard_map with the replication checker OFF.  The causal ring
    skips fully-masked blocks with a ``lax.cond`` whose predicate
    (``src <= rank``) is device-varying; both branches produce values
    varying over the same mesh axes, but the static rep/vma checker
    cannot type a varying-predicate cond and rejects the (correct)
    program — jax's own error message prescribes ``check_vma=False``
    as the workaround.  Gradient parity against the single-device
    oracle is pinned by tests/test_ring_attention.py."""
    import functools
    return functools.partial(jax.shard_map, check_vma=False)


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain softmax attention, (B, T, H, D) layout — the single-device
    oracle the parallel forms must match."""
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_attn(q, k, v, q_off, k_off, causal, scale, o, l, m):
    """One online-softmax accumulation step over a K/V block.
    q: (B, Tq, H, D); k/v: (B, Tk, H, D); o/l/m running stats."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        qpos = q_off + jnp.arange(tq)
        kpos = k_off + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (no valid key yet): keep them at zero
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = (o * alpha[..., None]
             + jnp.einsum("bhqk,bkhd->bhqd", p, v))
    return o_new, l_new, m_new


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None,
                   batch_axis=None):
    """Attention over sequences sharded on ``axis`` (see module doc).
    q/k/v: (B, T, H, D) global arrays (or shardable values); returns the
    (B, T, H, D) attention output with the same sharding. Pass
    ``batch_axis`` to compose with data parallelism (batch sharded over
    that mesh axis)."""
    from jax.sharding import PartitionSpec as P

    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    n = mesh.shape[axis]

    spec = P(batch_axis, axis, None, None)

    def local(ql, kl, vl):
        # ql/kl/vl: (B, T/n, H, D) local shards
        rank = lax.axis_index(axis)
        tq = ql.shape[1]
        b, h = ql.shape[0], ql.shape[2]
        o0 = jnp.zeros((b, h, tq, d), jnp.float32)
        l0 = jnp.zeros((b, h, tq), jnp.float32)
        m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
        # constants start device-invariant; mark them varying over every
        # sharded axis so the scan carry types line up (shard_map vma)
        vary_axes = tuple(a for a in (batch_axis, axis) if a)
        o0, l0, m0 = (lax.pcast(x, vary_axes, to="varying")
                      for x in (o0, l0, m0))
        perm = [(j, (j - 1) % n) for j in range(n)]

        # block 0 is local — no rotation; iterations 1..n-1 rotate THEN
        # compute, so exactly n-1 ppermutes happen per call (XLA overlaps
        # each transfer with the preceding block's compute on real ICI)
        k0 = kl.astype(jnp.float32)
        v0 = vl.astype(jnp.float32)
        o0, l0, m0 = _block_attn(ql, k0, v0, rank * tq, rank * tq,
                                 causal, scale, o0, l0, m0)

        def step(carry, i):
            o, l, m, k_cur, v_cur = carry
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
            src = (rank + i) % n            # block origin of k_cur

            def compute(olm):
                return _block_attn(ql, k_cur, v_cur, rank * tq, src * tq,
                                   causal, scale, *olm)

            if causal:
                # blocks strictly above the causal diagonal (src > rank)
                # are fully masked — skip their QK^T/PV entirely. (Load
                # stays imbalanced across the ring — the zigzag block
                # assignment that fixes it is a layout choice above this
                # kernel.)
                o, l, m = lax.cond(src <= rank, compute,
                                   lambda olm: olm, (o, l, m))
            else:
                o, l, m = compute((o, l, m))
            return (o, l, m, k_cur, v_cur), None

        if n > 1:
            (o, l, m, _, _), _ = lax.scan(
                step, (o0, l0, m0, k0, v0), jnp.arange(1, n))
        else:
            o, l, m = o0, l0, m0
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 2, 1, 3)).astype(ql.dtype)

    fn = _shard_map()(local, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh, axis="sp", causal=False, scale=None,
                      batch_axis=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses form): re-shard
    seq-split -> head-split, dense attention per head group, re-shard
    back. Requires num_heads %% mesh.shape[axis] == 0."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    h = q.shape[2]
    if h % n != 0:
        raise ValueError("ulysses_attention: %d heads not divisible by "
                         "sp=%d" % (h, n))
    spec = P(batch_axis, axis, None, None)

    def local(ql, kl, vl):
        # (B, T/n, H, D) -> (B, T, H/n, D)
        def fwd(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def bwd(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        out = attention_reference(fwd(ql), fwd(kl), fwd(vl),
                                  causal=causal, scale=scale)
        return bwd(out)

    fn = _shard_map()(local, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    return fn(q, k, v)
