"""mx.pallas: custom paged-attention kernels + donated KV-cache steps.

Covers the kernel library contract (docs/KERNELS.md): interpret-mode
parity of the Pallas paged decode/prefill/chunk-prefill kernels against
the XLA reference paths across cache geometries (block sizes, ragged
lengths, inactive slots, the OOB write sentinel, bf16 caches,
mid-prompt chunk starts over a live cache), the shared
``auto|<kernel>|xla`` dispatch semantics (``choose_impl``), the fused
2-bit quantize kernel's bit-exactness, the donated-cache decode step's
program-registry win (``bytes_accessed`` / ``peak_hbm_bytes`` strictly
below the copy-based step — the whole-cache per-launch copy is gone),
and a preemption-by-recompute equivalence rerun with the kernels
forced on.

Parity pin: rtol <= 2e-5 at f32 (conftest forces true f32 matmul
precision).  The decode kernel emits EXACT ZEROS for inactive slots
(pos < 0) where the XLA path emits masked don't-care values — parity
is asserted on active slots; both are masked by the engine.
"""
import functools
import os

import numpy as np

import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.pallas import (choose_impl, paged_chunk_prefill_attend,
                              paged_decode_attend, paged_prefill_attend,
                              two_bit_quantize_fused)
from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES

SEQ = 48
CFG = dict(num_classes=50, num_layers=2, d_model=16, num_heads=2,
           seq_len=SEQ)
RTOL = 2e-5


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)


def _decode_reference(q, k_cache, v_cache, table, pos, scale):
    """The XLA gather path's math (ops/nn.py), numpy-side."""
    q = np.asarray(q, np.float32)
    nb, bs, H, D = k_cache.shape
    kf = np.asarray(k_cache, np.float32).reshape(nb * bs, H, D)
    vf = np.asarray(v_cache, np.float32).reshape(nb * bs, H, D)
    C, M = table.shape
    out = np.zeros_like(q)
    for c in range(C):
        if pos[c] < 0:
            continue
        rows = [table[c, j // bs] * bs + j % bs for j in range(pos[c] + 1)]
        k = kf[rows]                                   # (ctx, H, D)
        v = vf[rows]
        s = np.einsum("he,jhe->hj", q[c], k) * scale
        s = s - s.max(axis=1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=1, keepdims=True)
        out[c] = np.einsum("hj,jhe->he", p, v)
    return out


# ----------------------------------------------------------------------
# kernel-level parity: decode
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bs,H,D", [(8, 2, 8), (16, 4, 4)])
def test_decode_kernel_parity_matrix(bs, H, D):
    """Ragged positions, an inactive slot, and a slot mid-first-block,
    across two block sizes."""
    rng = np.random.RandomState(3)
    nb, M, C = 10, 5, 4
    q = _rand(rng, C, H, D)
    kc = _rand(rng, nb, bs, H, D)
    vc = _rand(rng, nb, bs, H, D)
    table = rng.randint(0, nb, (C, M)).astype(np.int32)
    pos = np.array([bs - 2, 3 * bs + 1, -1, M * bs - 1], np.int32)
    sc = 1.0 / np.sqrt(D)
    out = paged_decode_attend(q, kc, vc, jnp.asarray(table),
                              jnp.asarray(pos), scale=sc,
                              interpret=True)
    ref = _decode_reference(q, kc, vc, table, pos, sc)
    active = pos >= 0
    np.testing.assert_allclose(np.asarray(out)[active], ref[active],
                               rtol=RTOL, atol=1e-6)
    # inactive slots come back EXACTLY zero (docs/KERNELS.md)
    np.testing.assert_array_equal(np.asarray(out)[~active], 0.0)


def test_decode_kernel_bf16_cache():
    """bf16 K/V cache, f32 accumulation inside the kernel."""
    rng = np.random.RandomState(4)
    nb, bs, H, D, C, M = 6, 8, 2, 8, 2, 3
    q = _rand(rng, C, H, D)
    kc = _rand(rng, nb, bs, H, D).astype(jnp.bfloat16)
    vc = _rand(rng, nb, bs, H, D).astype(jnp.bfloat16)
    table = rng.randint(0, nb, (C, M)).astype(np.int32)
    pos = np.array([2 * bs, bs - 1], np.int32)
    sc = 1.0 / np.sqrt(D)
    out = paged_decode_attend(q, kc, vc, jnp.asarray(table),
                              jnp.asarray(pos), scale=sc,
                              interpret=True)
    ref = _decode_reference(q, np.asarray(kc, np.float32),
                            np.asarray(vc, np.float32), table, pos, sc)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), ref, rtol=0.05, atol=0.05)


# ----------------------------------------------------------------------
# kernel-level parity: prefill (fused scatter)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bs,S", [(8, 16), (8, 11), (16, 13)])
def test_prefill_kernel_parity_and_scatter(bs, S):
    """Causal attention parity plus the fused cache scatter, including
    ragged S (padded up to a block multiple inside the wrapper) and
    rows past each length leaving old cache content untouched — the
    in-kernel analog of the XLA path's nb*bs OOB-drop sentinel."""
    rng = np.random.RandomState(5)
    B, H, D, nb = 2, 2, 8, 12
    M = -(-S // bs) + 1
    q = _rand(rng, B, S, H, D)
    k = _rand(rng, B, S, H, D)
    v = _rand(rng, B, S, H, D)
    kc = _rand(rng, nb, bs, H, D)
    vc = _rand(rng, nb, bs, H, D)
    table = np.zeros((B, M), np.int32)
    table[0, :] = (np.arange(M) + 1) % nb
    table[1, :] = (np.arange(M) + 5) % nb
    L = np.array([S, max(1, S - bs - 1)], np.int32)
    sc = 1.0 / np.sqrt(D)
    out, ko, vo = paged_prefill_attend(
        q, k, v, kc, vc, jnp.asarray(table), jnp.asarray(L), scale=sc,
        interpret=True)

    # attention reference: plain causal softmax, seq-major
    s = np.einsum("bqhe,bkhe->bhqk", np.asarray(q), np.asarray(k)) * sc
    mask = np.arange(S)[:, None] >= np.arange(S)[None, :]
    s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    ref = np.einsum("bhqk,bkhe->bqhe", p, np.asarray(v))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=RTOL, atol=1e-6)

    # scatter reference: rows < length land in their table block; every
    # other cache row is bit-identical to the input cache
    kfr = np.array(kc).reshape(nb * bs, H, D).copy()
    vfr = np.array(vc).reshape(nb * bs, H, D).copy()
    for b in range(B):
        for t in range(int(L[b])):
            row = table[b, t // bs] * bs + t % bs
            kfr[row] = np.asarray(k)[b, t]
            vfr[row] = np.asarray(v)[b, t]
    np.testing.assert_allclose(np.asarray(ko).reshape(nb * bs, H, D),
                               kfr, rtol=RTOL, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo).reshape(nb * bs, H, D),
                               vfr, rtol=RTOL, atol=1e-6)


@pytest.mark.parametrize("bs,S,K", [(8, 19, 8), (4, 13, 8), (8, 30, 16)])
def test_chunk_prefill_kernel_parity_with_unchunked(bs, S, K):
    """Chunk-aware prefill: feeding a prompt through
    paged_chunk_prefill_attend K tokens at a time over a live cache
    reproduces the one-shot paged_prefill_attend bit-for-bit in cache
    content and rtol-level in attention output (same math, different
    program) — including the clamp-onto-last-real-block sentinel for
    rows past each chunk's end."""
    rng = np.random.RandomState(21)
    B, H, D, nb = 1, 2, 8, 12
    M = -(-S // bs) + 1
    q = _rand(rng, B, S, H, D)
    k = _rand(rng, B, S, H, D)
    v = _rand(rng, B, S, H, D)
    kc = _rand(rng, nb, bs, H, D)
    vc = _rand(rng, nb, bs, H, D)
    table = ((np.arange(M) + 3) % nb).astype(np.int32).reshape(B, M)
    sc = 1.0 / np.sqrt(D)
    ref_o, ref_k, ref_v = paged_prefill_attend(
        q, k, v, kc, vc, jnp.asarray(table),
        jnp.asarray([S], jnp.int32), scale=sc, interpret=True)
    kcur, vcur = kc, vc
    outs = []
    st = 0
    while st < S:
        L = min(K, S - st)
        qp = jnp.zeros((B, K, H, D), jnp.float32).at[:, :L].set(
            q[:, st:st + L])
        kp = jnp.zeros((B, K, H, D), jnp.float32).at[:, :L].set(
            k[:, st:st + L])
        vp = jnp.zeros((B, K, H, D), jnp.float32).at[:, :L].set(
            v[:, st:st + L])
        o, kcur, vcur = paged_chunk_prefill_attend(
            qp, kp, vp, kcur, vcur, jnp.asarray(table),
            jnp.asarray([st], jnp.int32), jnp.asarray([L], jnp.int32),
            scale=sc, interpret=True)
        outs.append(np.asarray(o)[:, :L])
        st += L
    np.testing.assert_array_equal(np.asarray(ref_k), np.asarray(kcur))
    np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(vcur))
    np.testing.assert_allclose(np.concatenate(outs, axis=1),
                               np.asarray(ref_o), rtol=RTOL, atol=1e-6)


def test_chunk_prefill_kernel_zero_length_is_noop():
    """chunk_len == 0 (the idle mixed step) must leave the cache BYTE-
    identical: the clamped duplicate writes re-emit existing rows."""
    rng = np.random.RandomState(22)
    B, K, H, D, nb, bs, M = 1, 8, 2, 4, 6, 4, 3
    z = jnp.zeros((B, K, H, D), jnp.float32)
    kc = _rand(rng, nb, bs, H, D)
    vc = _rand(rng, nb, bs, H, D)
    table = jnp.zeros((B, M), jnp.int32)
    _, ko, vo = paged_chunk_prefill_attend(
        z, z, z, kc, vc, table, jnp.asarray([0], jnp.int32),
        jnp.asarray([0], jnp.int32), scale=0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(ko))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vo))


def test_prefill_kernel_rejects_short_table():
    rng = np.random.RandomState(6)
    B, S, H, D, nb, bs = 1, 16, 2, 4, 4, 4
    a = _rand(rng, B, S, H, D)
    kc = _rand(rng, nb, bs, H, D)
    table = jnp.zeros((B, 2), jnp.int32)            # needs 4 blocks
    with pytest.raises(ValueError, match="block_table"):
        paged_prefill_attend(a, a, a, kc, kc, table,
                             jnp.asarray([S], jnp.int32), scale=0.5,
                             interpret=True)


# ----------------------------------------------------------------------
# op-level parity: the _contrib ops under both impls
# ----------------------------------------------------------------------
def test_paged_decode_op_parity(monkeypatch):
    """pallas vs xla through _contrib_PagedDecodeAttention: active-slot
    outputs agree and the new caches are identical — the inactive slot
    (pos < 0) writes NOTHING under either impl (OOB sentinel)."""
    from mxnet_tpu.ops.nn import paged_decode_attention
    rng = np.random.RandomState(7)
    C, d, H, nb, bs, M = 3, 16, 2, 24, 4, 6
    D = d // H
    data = _rand(rng, C, 1, d)
    Wqkv, bqkv = _rand(rng, 3 * d, d), _rand(rng, 3 * d)
    Wp, bp = _rand(rng, d, d), _rand(rng, d)
    kc, vc = _rand(rng, nb, bs, H, D), _rand(rng, nb, bs, H, D)
    table = rng.permutation(nb)[:C * M].reshape(C, M).astype(np.float32)
    pos = np.array([[9.0], [21.0], [-1.0]], np.float32)

    def run():
        return paged_decode_attention(
            data, Wqkv, bqkv, Wp, bp, kc, vc, jnp.asarray(table),
            jnp.asarray(pos), num_heads=H)

    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    ox, kx, vx = run()
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    op_, kp, vp = run()
    active = pos.reshape(-1) >= 0
    np.testing.assert_allclose(np.asarray(ox)[active],
                               np.asarray(op_)[active],
                               rtol=RTOL, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(kx), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp))
    # the inactive slot wrote nothing: caches changed in exactly one
    # row per active slot
    changed = (np.asarray(kx) != np.asarray(kc)).any(axis=(2, 3)).sum()
    assert changed == active.sum()


@pytest.mark.parametrize("S,L", [(8, (7, 3)), (8, (8, 1))])
def test_paged_prefill_op_parity(monkeypatch, S, L):
    from mxnet_tpu.ops.nn import paged_prefill_attention
    rng = np.random.RandomState(8)
    B, d, H, nb, bs, M = 2, 16, 2, 16, 4, 6
    D = d // H
    data = _rand(rng, B, S, d)
    Wqkv, bqkv = _rand(rng, 3 * d, d), _rand(rng, 3 * d)
    Wp, bp = _rand(rng, d, d), _rand(rng, d)
    kc, vc = _rand(rng, nb, bs, H, D), _rand(rng, nb, bs, H, D)
    # disjoint per-row blocks — the allocator invariant; aliased REAL
    # entries across rows would make scatter order ambiguous under
    # EITHER impl
    table = rng.permutation(nb)[:B * M].reshape(B, M).astype(np.float32)
    lengths = np.asarray(L, np.float32).reshape(B, 1)

    def run():
        return paged_prefill_attention(
            data, Wqkv, bqkv, Wp, bp, kc, vc, jnp.asarray(table),
            jnp.asarray(lengths), num_heads=H)

    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    ox, kx, vx = run()
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    op_, kp, vp = run()
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op_),
                               rtol=RTOL, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kx), np.asarray(kp),
                               rtol=RTOL, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vx), np.asarray(vp),
                               rtol=RTOL, atol=1e-6)


def test_paged_chunk_prefill_op_parity(monkeypatch):
    """pallas vs xla through _contrib_PagedChunkPrefillAttention over a
    mid-prompt chunk (start > 0 against a live cache): outputs agree
    and new caches are bit-identical."""
    from mxnet_tpu.ops.nn import paged_chunk_prefill_attention
    rng = np.random.RandomState(19)
    B, K, d, H, nb, bs, M = 1, 8, 16, 2, 16, 4, 6
    D = d // H
    data = _rand(rng, B, K, d)
    Wqkv, bqkv = _rand(rng, 3 * d, d), _rand(rng, 3 * d)
    Wp, bp = _rand(rng, d, d), _rand(rng, d)
    kc, vc = _rand(rng, nb, bs, H, D), _rand(rng, nb, bs, H, D)
    table = rng.permutation(nb)[:B * M].reshape(B, M).astype(np.float32)
    start = np.asarray([5.0], np.float32)      # mid-prompt, mid-block
    lengths = np.asarray([6.0], np.float32)

    def run():
        return paged_chunk_prefill_attention(
            data, Wqkv, bqkv, Wp, bp, kc, vc, jnp.asarray(table),
            jnp.asarray(start), jnp.asarray(lengths), num_heads=H)

    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    ox, kx, vx = run()
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    op_, kp, vp = run()
    np.testing.assert_allclose(np.asarray(ox)[:, :6], np.asarray(op_)[:, :6],
                               rtol=RTOL, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kx), np.asarray(kp),
                               rtol=RTOL, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vx), np.asarray(vp),
                               rtol=RTOL, atol=1e-6)
    # exactly the chunk's 6 cache rows changed under both impls
    for knew in (kx, kp):
        changed = (np.asarray(knew) != np.asarray(kc)).any(
            axis=(2, 3)).sum()
        assert changed == 6


# ----------------------------------------------------------------------
# dispatch semantics (choose_impl — shared by all three knobs)
# ----------------------------------------------------------------------
def test_choose_impl_semantics():
    # xla always wins, even when supported
    assert choose_impl("MXNET_X", "xla", "pallas", True, why="w") is False
    # auto follows `supported`
    assert choose_impl("MXNET_X", "auto", "pallas", True,
                       why="w") == "compiled"
    assert choose_impl("MXNET_X", "auto", "pallas", False, why="w",
                       count=False) is False
    # forcing the kernel honors force_supported, and is the one way
    # to interpret mode: never where the kernel compiles, never on auto
    assert choose_impl("MXNET_X", "pallas", "pallas", False, why="w",
                       force_supported=True) == "interpret"
    assert choose_impl("MXNET_X", "pallas", "pallas", True, why="w",
                       force_supported=True) == "compiled"
    with pytest.raises(ValueError, match="cannot run here"):
        choose_impl("MXNET_X", "pallas", "pallas", False, why="w")
    with pytest.raises(ValueError, match=r"use auto\|pallas\|xla"):
        choose_impl("MXNET_X", "bogus", "pallas", True, why="w")


def test_flash_and_paged_knobs_share_one_contract(monkeypatch):
    """The flash pair's choice has no knob and is counted: on the CPU it
    is the XLA core under ``pallas_fallbacks{reason="backend"}`` whatever
    the environment says, in a one-device TPU program the kernels, in one
    partitioned over a selected mesh ``mesh``, at a shape they refuse
    ``flash-geometry`` (only a test hands ``_flash_attention``
    ``interpret=True``).  The paged knob keeps ``choose_impl``'s
    contract: forced off-TPU it runs via interpret mode."""
    from mxnet_tpu.ops.nn import _use_flash_attention
    from mxnet_tpu.pallas.dispatch import use_paged_pallas
    count = lambda reason: PALLAS_FALLBACKS.labels(reason=reason).value
    before = count("backend")
    for value in ("flash", "xla", "bogus"):     # the name the knob had
        monkeypatch.setenv("MXNET_ATTN_IMPL", value)
        assert _use_flash_attention(512, 128, jnp.float32) is False
    assert count("backend") == before + 3
    monkeypatch.delenv("MXNET_ATTN_IMPL")
    environ = dict(os.environ)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert _use_flash_attention(512, 128, jnp.float32) == "compiled"
        before = count("flash-geometry"), count("mesh")
        assert _use_flash_attention(512, 96, jnp.float32) is False
        mx.sharding.set_mesh({"dp": 4, "mp": 2})
        try:
            assert _use_flash_attention(512, 128, jnp.float32) is False
        finally:
            mx.sharding.set_mesh(None)
        assert (count("flash-geometry"), count("mesh")) \
            == (before[0] + 1, before[1] + 1)
    assert dict(os.environ) == environ
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    assert use_paged_pallas() == "interpret"
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    assert use_paged_pallas() is False
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "bogus")
    with pytest.raises(ValueError, match=r"use auto\|pallas\|xla"):
        use_paged_pallas()


@pytest.mark.parametrize("window", [None, 4], ids=["causal", "band4"])
@pytest.mark.parametrize("Hq,Hk", [(4, 4), (4, 2)], ids=["h4to4", "h4to2"])
@pytest.mark.parametrize("chosen", ["xla", "flash"])
def test_the_causal_core_hands_a_front_its_fold_and_its_attend(
        monkeypatch, chosen, Hq, Hk, window):
    """``_causal_attention_core`` is asked once a call and answers with
    ``(fold, attend)``.  Where the XLA core runs (the CPU, a mesh, a
    shape the kernels refuse) it applies the scale itself: the fold is
    1.0 and ``attend`` is ``_grouped_causal_attention`` with the scale
    and the band, to the bit.  Where the flash pair runs the fold IS the
    scale (the kernels take none) and ``attend`` hands q, k, v on
    untouched, the band by keyword: with q scaled by the fold the two
    answers agree."""
    from mxnet_tpu.ops import nn
    B, S, D, scale = 2, 12, 8, 0.3
    ks = jax.random.split(jax.random.PRNGKey(Hq * 10 + Hk), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hk, S, D))
    v = jax.random.normal(ks[2], (B, Hk, S, D))
    want = nn._grouped_causal_attention(q, k, v, scale, window)
    asked, seen = [], []
    if chosen == "flash":
        def gate(*a):
            asked.append(a)
            return "compiled"

        def kernels(q, k, v, *, window=None):
            # what the pair computes: q already carries the scale
            seen.append(window)
            return nn._grouped_causal_attention(q, k, v, 1.0, window)

        monkeypatch.setattr(nn, "_use_flash_attention", gate)
        monkeypatch.setattr(nn, "_flash_attention", kernels)
    fold, attend = nn._causal_attention_core(S, D, q.dtype, scale, None,
                                             window)
    if chosen == "xla":
        assert fold == 1.0
        assert np.array_equal(np.asarray(attend(q, k, v)), np.asarray(want))
    else:
        assert fold == scale and asked == [(S, D, q.dtype, None, window)]
        got = attend(q * fold, k, v)
        assert seen == [window]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)


def _materialised_causal_attention(q, k, v):
    """The checkpointed materialised-softmax path of ops/nn.py, grouped:
    q (B, Hq, S, D) carries the softmax scale, k/v are (B, Hk, S, D)."""
    B, Hq, S, D = q.shape
    Hk = k.shape[1]

    @jax.checkpoint
    def attn(q, k, v):
        s = jnp.einsum("bgrqe,bgke->bgrqk",
                       q.reshape(B, Hk, Hq // Hk, S, D), k)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrqk,bgke->bgrqe", p, v).reshape(B, Hq, S, D)

    return attn(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Hq,Hk,S", [(4, 4, 512), (8, 2, 1024)],
                         ids=["h4to4_s512", "h8to2_s1024"])
def test_flash_attention_parity_fwd_bwd(Hq, Hk, S, dtype):
    """The flash kernels ops/nn.py runs (jax's splash attention forward,
    the repo's backward behind it, key/value heads shared by their query
    heads inside both), interpreted: value and dq, dk, dv against the
    materialised-softmax path, equal head counts and ZAYA's 4 : 1
    grouping with K and V at their own head count, batch 2."""
    from mxnet_tpu.ops.nn import _flash_attention
    B, D = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(Hq * S), 4)
    q = (jax.random.normal(ks[0], (B, Hq, S, D)) * D ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hk, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hk, S, D)).astype(dtype)
    w = jax.random.normal(ks[3], (B, Hq, S, D))

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    from mxnet_tpu.pallas.flash_backward import _run_pass
    _run_pass.clear_cache()     # the backward kernel is built in this test
    launches = [PALLAS_LAUNCHES.labels(kernel=name)
                for name in ("flash_attention", "flash_attention_bwd")]
    before = [c.value for c in launches]
    got = run(lambda q, k, v: _flash_attention(q, k, v, interpret=True))
    assert [c.value for c in launches] == [n + 1 for n in before]
    want = run(_materialised_causal_attention)
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    # float32: the two orders of summation; bf16: one rounding of p and
    # of each result, against gradients of size ~10-300
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


def _float32_causal_attention(q, k, v):
    """Causal attention in float32 whatever the operands' dtype, grouped:
    the result (B, Hq, S, Dv) and the rows' log-sum-exp (B, Hq, S)."""
    B, Hq, S, D = q.shape
    Hk = k.shape[1]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bgrqe,bgke->bgrqk", q.reshape(B, Hk, Hq // Hk, S, D), k)
    s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(S)[None, :], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bgrqk,bgke->bgrqe", jnp.exp(s - lse[..., None]), v)
    return o.reshape(B, Hq, S, -1), lse.reshape(B, Hq, S)


def _flash_backward_operands(B, Hq, Hk, S, D, Dv, dtype):
    """q (scaled), k, v, the float32 attention's o and log-sum-exp, a
    cotangent, and the float32 attention's dq, dk, dv for it."""
    ks = jax.random.split(jax.random.PRNGKey(Hq * S + D), 4)
    q = (jax.random.normal(ks[0], (B, Hq, S, D)) * D ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hk, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hk, S, Dv)).astype(dtype)
    do = jax.random.normal(ks[3], (B, Hq, S, Dv)).astype(dtype)
    (o, lse), vjp = jax.vjp(_float32_causal_attention, q, k, v)
    want = vjp((do.astype(jnp.float32), jnp.zeros_like(lse)))
    return (q, k, v, o.astype(dtype), lse, do), want


@pytest.mark.parametrize("B,Hq,Hk,S,D,Dv,dtype", [
    (1, 2, 2, 512, 128, 128, jnp.float32),
    (2, 4, 1, 1536, 128, 128, jnp.bfloat16),
    (1, 8, 1, 2048, 256, 256, jnp.bfloat16),
    (1, 2, 2, 1536, 192, 128, jnp.float32),
    (2, 2, 2, 512, 256, 256, jnp.bfloat16),
    (1, 4, 1, 2048, 192, 128, jnp.bfloat16),
    (2, 8, 1, 512, 128, 128, jnp.float32),
    (1, 2, 2, 2048, 128, 128, jnp.bfloat16),
    (1, 4, 1, 512, 192, 128, jnp.float32),
    (1, 8, 1, 1536, 256, 256, jnp.float32),
], ids=lambda x: getattr(x, "__name__", str(x)))
def test_flash_backward_kernel_against_float32_attention(B, Hq, Hk, S, D, Dv,
                                                         dtype):
    """The repo's backward kernel alone, interpreted, on the float32
    attention's own o and log-sum-exp: dq, dk, dv against that
    attention's gradients, over equal heads and 4 : 1 and 8 : 1 groups
    (K and V at their own head count), one width and two (192 has dq
    and dk summed transposed), one block of 512 rows, three and four,
    one sequence and two.  (One block of 192-wide bfloat16 is left out:
    there XLA's CPU backend folds the interpreted kernel's slices and
    transposes into a bfloat16 product it has no routine for.)"""
    from mxnet_tpu.pallas.flash_backward import flash_attention_backward
    operands, want = _flash_backward_operands(B, Hq, Hk, S, D, Dv, dtype)
    got = flash_attention_backward(*operands, interpret=True)
    # float32: the two orders of summation; bf16: o, p and ds rounded
    # once, each result once
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, like in zip(got, want, operands):
        assert a.shape == like.shape and a.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_backward_in_two_segments_is_the_unsegmented(monkeypatch,
                                                           dtype):
    """A VMEM budget that holds half of a key/value head's 2048 rows cuts
    them into two passes, the second starting from the first's float32
    dq: the same sums in the same order, so every bit of dq, dk, dv is
    the one pass's."""
    from mxnet_tpu.pallas import flash_backward as fb
    operands, _ = _flash_backward_operands(1, 4, 2, 2048, 192, 128, dtype)
    whole = fb.flash_attention_backward(*operands, interpret=True)
    small = fb.plan(1024, 192, 128, dtype).vmem_limit_bytes - fb._WORKING
    assert fb.plan(2048, 192, 128, dtype, small)[:2] == (2, 1024)
    monkeypatch.setattr(fb, "plan",
                        functools.partial(fb.plan, budget=small))
    halves = fb.flash_attention_backward(*operands, interpret=True)
    for a, b in zip(halves, whole):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _avals(jaxpr):
    """Every array type a jaxpr names, its sub-jaxprs' included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_flash_gradient_makes_no_dq_partials():
    """The program of a gradient through ``_flash_attention``: jax's
    forward kernel, the repo's backward kernel and not jax's, and no
    array with an axis of partial sums before q's (jax's fused backward
    wrote S / block copies of dq and summed them afterwards)."""
    from mxnet_tpu.ops.nn import _flash_attention
    B, Hq, Hk, S, D = 2, 4, 2, 1024, 128
    q = jnp.zeros((B, Hq, S, D), jnp.bfloat16)
    k = v = jnp.zeros((B, Hk, S, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: _flash_attention(q, k, v, interpret=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    text = str(jaxpr)
    assert "splash_mha_fwd" in text and "flash_attention_backward" in text
    assert "splash_mha_dkv" not in text
    shapes = {tuple(a.shape) for a in _avals(jaxpr.jaxpr)
              if hasattr(a, "shape")}
    assert (B, Hq, S, D) in shapes
    assert not [s for s in shapes if len(s) > 4 and s[-3:] == (Hq, S, D)]


@pytest.mark.parametrize("name,S,fwd,held", [
    ("cgpt13b_train_s2048", 2048, 1024, 6.3e6),
    ("zaya1_8b_train_ep2", 8192, 1024, 25.2e6),
    ("smallest", 512, 512, 1.6e6),
    ("odd_multiple", 1536, 512, 4.7e6),
])
def test_flash_block_sizes_divide_the_sequence(name, S, fwd, held):
    """One function of the shapes gives each kernel its tiles: the
    forward's blocks divide S and its compute block its resident block,
    it carries no backward fields (jax's backward is not called); the
    backward holds a key/value head's whole S rows in one segment, and
    its VMEM limit is what those rows take (bf16, head_dim 128) beside
    the working room."""
    from mxnet_tpu.ops.nn import _flash_block_sizes
    from mxnet_tpu.pallas import flash_backward as fb
    bs = _flash_block_sizes(S)
    assert (bs.block_q, bs.block_kv, bs.block_kv_compute) == (fwd, fwd, 512)
    assert S % bs.block_q == 0 and bs.block_kv % bs.block_kv_compute == 0
    assert not bs.has_backward_blocks and not bs.use_fused_bwd_kernel
    z = fb.plan(S, 128, 128, jnp.bfloat16)
    assert (z.segments, z.rows, z.transposed) == (1, S, False), (name, z)
    assert z.rows % fb._BLOCK == 0
    assert abs(z.vmem_limit_bytes - fb._WORKING - held) < 0.05e6, (name, z)


def _flash_ops():
    """The four operators that take the flash kernel, at the smallest
    geometry its gate lets through (S 512, heads of 128), float32:
    name -> (function of its array arguments, the arguments)."""
    from mxnet_tpu.ops import nn
    rng = np.random.RandomState(27)
    S, D, H = 512, 128, 2
    d = H * D
    x = _rand(rng, 1, S, d)
    Wqkv, bqkv = _rand(rng, 3 * d, d) * 0.1, _rand(rng, 3 * d)
    Wp, bp = _rand(rng, d, d) * 0.1, _rand(rng, d)
    nb, bs = S // 16, 16
    cache = jnp.zeros((nb, bs, H, D), jnp.float32)
    table = jnp.arange(nb, dtype=jnp.float32).reshape(1, nb)
    Hq, Hk, dm = 4, 2, 64
    cca = [_rand(rng, Hq * D, dm), _rand(rng, Hk * D, dm),
           _rand(rng, 2 * D, dm), _rand(rng, (Hq + Hk) * D, 2),
           _rand(rng, Hq + Hk, D, D, 2) * 0.1,
           jnp.asarray([0.8, 1.3], jnp.float32), _rand(rng, dm, Hq * D)]
    return {
        "CausalSelfAttention": (
            lambda qkv: nn.causal_self_attention(qkv, num_heads=H),
            [_rand(rng, 1, S, 3 * d)]),
        "FusedCausalSelfAttention": (
            lambda *a: nn.fused_causal_self_attention(*a, num_heads=H),
            [x, Wqkv, bqkv, Wp, bp]),
        "CompressedConvAttention": (
            lambda *a: nn.compressed_conv_attention(
                *a, q_heads=Hq, kv_heads=Hk, head_dim=D),
            [_rand(rng, 1, S, dm)] + cca),
        "PagedPrefillAttention": (
            lambda *a: nn.paged_prefill_attention(
                *a, cache, cache, table,
                jnp.full((1, 1), S, jnp.float32), num_heads=H)[0],
            [x, Wqkv, bqkv, Wp, bp]),
    }


@pytest.mark.parametrize("op", ["CausalSelfAttention",
                                "FusedCausalSelfAttention",
                                "CompressedConvAttention",
                                "PagedPrefillAttention"])
def test_flash_branch_of_each_operator_matches_its_xla_branch(
        monkeypatch, op):
    """The kernel takes no softmax scale, so each caller folds it into
    q on its flash branch only.  The branch is steered as the chip
    would answer and the kernel interpreted: output and every gradient
    agree with the operator's own XLA branch."""
    from mxnet_tpu.ops import nn
    fn, args = _flash_ops()[op]
    w = _rand(np.random.RandomState(3), *fn(*args).shape)

    def run():
        return jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(),
            argnums=tuple(range(len(args))))(*args)

    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    want = run()
    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    kernel = nn._flash_attention
    monkeypatch.setattr(
        nn, "_flash_attention",
        lambda q, k, v, window=None: kernel(q, k, v, window=window,
                                            interpret=True))
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(b).max() > 0
        assert np.abs(a - b).max() <= 5e-5 * np.abs(b).max()


def test_flash_kernel_is_built_once_per_geometry():
    """The mask's block tables are host work at trace time: every layer
    of a model asks for the same kernel object (one for the value alone,
    one that keeps the log-sum-exp for a gradient)."""
    from mxnet_tpu.ops.nn import _flash_kernel
    a = _flash_kernel(4, 512, True)
    assert _flash_kernel(4, 512, True) is a
    assert _flash_kernel(2, 512, True) is not a
    assert _flash_kernel(4, 512, True, True) is not a
    # kept on the host: constants of whatever program uses them
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(a))


def test_fallback_counter_and_launch_witnesses(monkeypatch):
    """auto off-TPU books one pallas_fallbacks{reason=backend}; a
    kernel call books pallas_kernel_launches{kernel=...}; observer
    calls (count=False) book nothing."""
    from mxnet_tpu.pallas.dispatch import paged_attn_impl, use_paged_pallas
    monkeypatch.delenv("MXNET_PAGED_ATTN_IMPL", raising=False)
    fb = PALLAS_FALLBACKS.labels(reason="backend")
    before = fb.value
    assert use_paged_pallas() is False       # CPU container: auto -> xla
    assert fb.value == before + 1
    assert paged_attn_impl() == "xla"        # observer: no bump
    assert fb.value == before + 1
    lc = PALLAS_LAUNCHES.labels(kernel="paged_decode_attend")
    lb = lc.value
    rng = np.random.RandomState(9)
    paged_decode_attend(_rand(rng, 1, 2, 4), _rand(rng, 2, 4, 2, 4),
                        _rand(rng, 2, 4, 2, 4),
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.asarray([3], jnp.int32), scale=0.5,
                        interpret=True)
    assert lc.value == lb + 1


# ----------------------------------------------------------------------
# fused 2-bit quantize (stretch kernel)
# ----------------------------------------------------------------------
def test_two_bit_quantize_kernel_bit_exact(monkeypatch):
    """Kernel vs the shared XLA sequence (kvstore_fused): identical op
    order and constants, therefore identical bits — including through
    the MXNET_Q2BIT_IMPL dispatch inside two_bit_quantize itself."""
    from mxnet_tpu.kvstore_fused import two_bit_quantize
    rng = np.random.RandomState(10)
    for shape in [(3, 1000), (777,), (64, 128)]:
        res = _rand(rng, *shape)
        grad = _rand(rng, *shape)
        monkeypatch.setenv("MXNET_Q2BIT_IMPL", "xla")
        q_ref, r_ref = two_bit_quantize(res, grad, 0.5)
        q_k, r_k = two_bit_quantize_fused(res, grad, 0.5, interpret=True)
        np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_ref))
        monkeypatch.setenv("MXNET_Q2BIT_IMPL", "pallas")
        q_d, r_d = two_bit_quantize(res, grad, 0.5)
        np.testing.assert_array_equal(np.asarray(q_d), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(r_d), np.asarray(r_ref))


# ----------------------------------------------------------------------
# engine integration: donated caches + kernels end to end
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    tsym = transformer.get_symbol(**CFG)
    arg_shapes, _, _ = tsym.infer_shape(data=(1, SEQ), softmax_label=(SEQ,))
    rng = np.random.RandomState(7)
    params = {n: rng.normal(0, 0.1, s).astype(np.float32)
              for n, s in zip(tsym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return params


def _engine(params, **kw):
    from mxnet_tpu.decode import DecodeEngine
    kw.setdefault("capacity", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 36)
    kw.setdefault("chunk_tokens", 8)
    return DecodeEngine(params, CFG, **kw)


def _decode_step_programs():
    """The mixed-step executor programs (the (capacity, table_width)
    block table identifies the engine's ONE compiled step under both
    the copy and donated arg orders)."""
    return [p for p in telemetry.programs(site="executor")
            if any(s.endswith("[3, 12]") for s in p["arg_shapes"])]


def test_donated_step_drops_whole_cache_copy(model, monkeypatch):
    """THE acceptance pin: with MXNET_DECODE_DONATE the compiled mixed
    step aliases the k/v caches in place — compiler-reported
    peak_hbm_bytes drops by at least half a cache footprint vs the
    copy-based step, and bytes_accessed never regresses (asserted via
    telemetry.programs(), not wall-clock)."""
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "xla")
    cache_bytes = 2 * CFG["num_layers"] * 36 * 4 * 2 * 8 * 4  # k+v, f32

    def step_prog(donate):
        monkeypatch.setenv("MXNET_DECODE_DONATE", donate)
        telemetry.programs.clear()
        eng = _engine(model, warmup=True, start=True)
        try:
            list(eng.submit([5, 6, 7], max_new_tokens=4))
            progs = _decode_step_programs()
        finally:
            eng.stop()
        assert len(progs) == 1
        return progs[0]

    copy = step_prog("0")
    donated = step_prog("1")
    assert copy["fn_name"] == "_fwd_eval"
    assert donated["fn_name"] == "_fwd_eval_donated"
    # donation never costs bytes (the chunk stream's second scatter
    # chains in place either way on the cost model)...
    assert donated["bytes_accessed"] <= copy["bytes_accessed"]
    # ...and the step's high-water mark loses the staging copy of the
    # caches: at least half a cache footprint off peak
    assert donated["peak_hbm_bytes"] <= copy["peak_hbm_bytes"] \
        - cache_bytes // 2


def test_engine_tokens_invariant_under_impl_and_donation(model,
                                                         monkeypatch):
    """Greedy outputs are identical across {xla, pallas} x {copy,
    donated} — four engines, one token stream."""
    prompts = [[5, 6, 7], [1, 2]]
    outs = {}
    for impl in ("xla", "pallas"):
        for donate in ("0", "1"):
            monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", impl)
            monkeypatch.setenv("MXNET_DECODE_DONATE", donate)
            eng = _engine(model, warmup=False, start=True)
            try:
                hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                outs[(impl, donate)] = [h.result(timeout=120) for h in hs]
                st = eng.stats()
                assert st["steady_state_retraces"] == 0
                assert st["dispatches_per_step"] == 1.0
                assert st["attn_impl"] == impl
                assert st["cache_donation"] == (donate == "1")
            finally:
                eng.stop()
    ref = outs[("xla", "0")]
    assert all(v == ref for v in outs.values())


def test_preemption_equivalence_under_pallas(model, monkeypatch):
    """test_decode.py's preemption-by-recompute equivalence, rerun with
    the Pallas kernels forced on (interpret mode): eviction + prefill
    recompute over donated caches reproduces the uncontended stream."""
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    un = _engine(model, warmup=False, start=True)
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    try:
        ref = [un.generate(p, max_new_tokens=10, timeout=120)
               for p in prompts]
    finally:
        un.stop()
    eng = _engine(model, capacity=4, num_blocks=7, warmup=False,
                  start=True)
    try:
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [h.result(timeout=120) for h in hs]
        st = eng.stats()
        assert st["preemptions"] > 0
        assert st["steady_state_retraces"] == 0
        assert st["cache"]["blocks_free"] == st["cache"]["num_blocks"]
        assert outs == ref
    finally:
        eng.stop()


# ----------------------------------------------------------------------
# fused LayerNorm (+residual) — registry-ranked kernel (docs/KERNELS.md)
# ----------------------------------------------------------------------
def _ln_jnp(x, g, b, res=None, eps=1e-5):
    """Pure-jnp reference (the ops/nn.py fallback math)."""
    xx = x + res if res is not None else x
    mean = jnp.mean(xx, axis=-1, keepdims=True)
    var = jnp.mean((xx - mean) ** 2, axis=-1, keepdims=True)
    return (xx - mean) * jax.lax.rsqrt(var + eps) * g + b


@pytest.mark.parametrize("with_res", [False, True])
@pytest.mark.parametrize("shape", [(4, 33), (3, 5, 48)])
def test_layernorm_kernel_parity_fwd_bwd(with_res, shape):
    """layernorm_fused (interpret mode) vs the jnp reference: forward
    plus every input gradient, with non-lane-aligned feature dims (33)
    and rows that don't fill the 8-row tile — the masked-padding paths
    of _ln_forward/_ln_backward."""
    from mxnet_tpu.pallas import layernorm_fused
    rng = np.random.RandomState(21)
    cols = shape[-1]
    x = _rand(rng, *shape)
    res = _rand(rng, *shape) if with_res else None
    g, b = _rand(rng, cols), _rand(rng, cols)
    dy = _rand(rng, *shape)

    out, mean, rstd = layernorm_fused(x, g, b, residual=res,
                                      interpret=True)
    ref = _ln_jnp(x, g, b, res)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=1e-6)
    xx = x + res if with_res else x
    np.testing.assert_allclose(np.asarray(mean),
                               np.asarray(jnp.mean(xx, axis=-1)),
                               rtol=RTOL, atol=1e-6)
    assert out.shape == x.shape and mean.shape == x.shape[:-1]

    def loss_kernel(*args):
        o, _, _ = layernorm_fused(args[0], args[1], args[2],
                                  residual=args[3] if with_res else None,
                                  interpret=True)
        return jnp.sum(o * dy)

    def loss_ref(*args):
        return jnp.sum(_ln_jnp(args[0], args[1], args[2],
                               args[3] if with_res else None) * dy)

    argnums = (0, 1, 2, 3) if with_res else (0, 1, 2)
    args = (x, g, b, res) if with_res else (x, g, b)
    gk = jax.grad(loss_kernel, argnums=argnums)(*args)
    gr = jax.grad(loss_ref, argnums=argnums)(*args)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)


def test_layernorm_op_parity(monkeypatch):
    """pallas vs xla through the registered LayerNorm op: outputs and
    every gradient agree, under jit, including the backward routed
    through the fused _ln_backward kernel."""
    from mxnet_tpu.ops.nn import layer_norm
    rng = np.random.RandomState(22)
    x = _rand(rng, 6, 33)
    g, b = _rand(rng, 33), _rand(rng, 33)
    dy = _rand(rng, 6, 33)

    def run():
        def loss(x, g, b):
            out, _, _ = layer_norm(x, g, b)
            return jnp.sum(out * dy)
        out, _, _ = jax.jit(lambda *a: layer_norm(*a))(x, g, b)
        grads = jax.grad(loss, argnums=(0, 1, 2))(x, g, b)
        return out, grads

    monkeypatch.setenv("MXNET_LN_IMPL", "xla")
    ox, gx = run()
    monkeypatch.setenv("MXNET_LN_IMPL", "pallas")
    op_, gp = run()
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op_),
                               rtol=RTOL, atol=1e-6)
    for a, r in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)


def test_layernorm_knob_contract(monkeypatch):
    """MXNET_LN_IMPL rides the same choose_impl contract as every other
    kernel knob: xla always wins, auto falls back off-TPU, forcing
    pallas runs interpret mode but still requires axis=-1."""
    from mxnet_tpu.pallas import use_layernorm_pallas
    monkeypatch.setenv("MXNET_LN_IMPL", "xla")
    assert use_layernorm_pallas(True) is False
    monkeypatch.setenv("MXNET_LN_IMPL", "auto")
    assert use_layernorm_pallas(True) is False      # CPU container
    monkeypatch.setenv("MXNET_LN_IMPL", "pallas")
    assert use_layernorm_pallas(True) == "interpret"
    with pytest.raises(ValueError, match="cannot run here"):
        use_layernorm_pallas(False)                 # axis != -1
    monkeypatch.setenv("MXNET_LN_IMPL", "bogus")
    with pytest.raises(ValueError, match=r"use auto\|pallas\|xla"):
        use_layernorm_pallas(True)


def test_layernorm_transformer_witness(monkeypatch):
    """Forced on, the kernel serves the transformer symbol path: the
    bound forward books pallas_kernel_launches{kernel=layernorm_fused}
    and the containing executor program lands in telemetry.programs()."""
    monkeypatch.setenv("MXNET_LN_IMPL", "pallas")
    telemetry.programs.clear()
    lc = PALLAS_LAUNCHES.labels(kernel="layernorm_fused")
    before = lc.value
    sym_lm = transformer.get_symbol(**CFG)
    mod = mx.mod.Module(sym_lm, data_names=["data"],
                        label_names=["softmax_label"], context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, SEQ))],
             label_shapes=[("softmax_label", (2, SEQ))],
             for_training=False)
    mod.init_params(mx.init.Normal(0.02))
    batch = mx.io.DataBatch(
        data=[mx.nd.array(np.ones((2, SEQ), np.float32))], label=None)
    mod.forward(batch, is_train=False)
    mod.get_outputs()[0].asnumpy()
    assert lc.value > before          # kernel actually launched
    progs = telemetry.programs(analyze=False, site="executor")
    assert progs, "bound forward must register an executor program"
