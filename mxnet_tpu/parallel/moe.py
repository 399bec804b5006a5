"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

The reference (MXNet ~1.2) predates MoE entirely (SURVEY.md §2.3 lists
expert parallelism among the absent modern strategies), so — like ring
attention and the GPipe pipeline — this is a new TPU-native capability:
Switch/top-k routing in the Mesh-TensorFlow einsum formulation (static
shapes, no data-dependent gather loops — exactly what XLA wants), with
the expert-stacked parameters and the dispatched token blocks sharded
over ``ep`` via ``with_sharding_constraint`` so GSPMD inserts the
all-to-alls that move token blocks to their experts over ICI.

* ``switch_moe``      — routed expert-FFN layer: returns (y, aux_loss)
  where aux_loss is the standard load-balancing loss (Switch
  Transformer eq. 4: E * Σ_e f_e · P_e).
* ``moe_reference``   — dense oracle: every token through every
  expert, mixed by the FULL softmax over all experts. It equals
  switch_moe only when ``k == n_experts`` and no token overflows
  capacity (the tests pin exactly that case, plus a separate top-1
  oracle); for ``k < n_experts`` switch_moe combines with the
  un-renormalized top-k probabilities, so the two differ even with
  infinite capacity.

Capacity semantics: each expert processes at most
``ceil(k·N/E · capacity_factor)`` tokens; overflowing tokens are
dropped from that expert (their combine weight is zero), the standard
Switch behavior.

Which routine drops and which does not: ``switch_moe`` DROPS (capacity,
dense one-hot dispatch ``(N, E, C)``); ``dropless_top1_experts`` never
does.  It is the expert layer of a chip that holds a SHARE of the
experts (``model-configs`` guide, section 4): probabilities come over
all experts, the tokens of the experts held here are sorted by expert
and run through three grouped matrix products (gate, up, down of a
SiLU-gated FFN; ``grouped_matmul``: the Pallas kernel where it compiles,
``jax.lax.ragged_dot_general`` elsewhere), and a token whose expert
lives elsewhere contributes 0.  No capacity, no exchange, and nothing that
stands in for the absent chips; ``zaya_router`` is the router that
goes with it (docs/TRAINING.md, "The dropless expert layer"), and
``linear_router`` and ``sigmoid_router`` feed its top-k form.
"""
from __future__ import annotations

import functools as _functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["switch_moe", "moe_reference", "init_moe_params",
           "zaya_router", "linear_router", "sigmoid_router", "gated_ffn",
           "dropless_topk_experts", "dropless_top1_experts"]


def init_moe_params(key, d_model, d_hidden, n_experts, dtype=jnp.float32):
    """Router + expert-stacked FFN parameters (leading axis E — the one
    that shards over ``ep``)."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts),
                                     jnp.float32) * s1).astype(dtype),
        "w1": (jax.random.normal(k2, (n_experts, d_model, d_hidden),
                                 jnp.float32) * s1).astype(dtype),
        "b1": jnp.zeros((n_experts, d_hidden), dtype),
        "w2": (jax.random.normal(k3, (n_experts, d_hidden, d_model),
                                 jnp.float32) * s2).astype(dtype),
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _expert_ffn(params, xe):
    """xe: (E, C, d) — each expert's token block through its own FFN."""
    h = jnp.einsum("ecd,edh->ech", xe, params["w1"]) \
        + params["b1"][:, None, :]
    h = jax.nn.relu(h)
    return jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
        + params["b2"][:, None, :]


def moe_reference(params, x):
    """Dense oracle: every token through every expert, weighted by the
    full softmax gate — the no-capacity-limit ideal."""
    probs = jax.nn.softmax(x @ params["router"], axis=-1)      # (N, E)
    h = jnp.einsum("nd,edh->neh", x, params["w1"]) \
        + params["b1"][None]
    h = jax.nn.relu(h)
    y = jnp.einsum("neh,ehd->ned", h, params["w2"]) \
        + params["b2"][None]
    return jnp.einsum("ne,ned->nd", probs, y)


def switch_moe(params, x, k=1, capacity_factor=1.25, mesh=None,
               axis="ep"):
    """Top-k routed MoE layer. x: (N, d_model) tokens (flatten (B, T)
    outside). Returns (y, aux_loss).

    With ``mesh``, the expert-stacked tensors are sharding-constrained
    to P(axis) on their leading E dim — under jit over that mesh, GSPMD
    partitions the expert FFNs across ``ep`` and inserts the
    all-to-alls for the dispatch/combine einsums.
    """
    N, d = x.shape
    E = params["router"].shape[1]
    k = int(k)
    C = max(1, int(math.ceil(k * N / E * float(capacity_factor))))

    def constrain(v):
        """Pin the leading (expert) axis to the ep mesh axis."""
        if mesh is None:
            return v
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(axis, *([None] * (v.ndim - 1)))
        return lax.with_sharding_constraint(
            v, NamedSharding(mesh, spec))

    logits = x @ params["router"]                               # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, k)                   # (N, k)

    # position of each (token, choice) in its expert's queue: running
    # count of earlier assignments to the same expert (einsum-style
    # cumsum dispatch — static shapes, no sorting)
    assign = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)       # (N,k,E)
    flat = assign.reshape(N * k, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat             # (N*k, E)
    pos = (pos_in_expert * flat).sum(-1).reshape(N, k)          # (N, k)
    keep = pos < C
    gate_vals = gate_vals * keep                                # drop overflow

    # dispatch (N, k, E, C) one-hots contracted on the fly
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C,
                            dtype=x.dtype)                      # (N,k,C)
    disp = jnp.einsum("nke,nkc->nec", assign.astype(x.dtype),
                      pos_oh * keep[..., None])                 # (N,E,C)
    xe = jnp.einsum("nec,nd->ecd", disp, x)                     # (E,C,d)
    xe = constrain(xe)

    # expert-parallel FFN: the expert-stacked params (by NAME — a shape
    # test would misfire when d_model == n_experts) shard over ep
    eparams = {kk: (constrain(v) if kk in ("w1", "b1", "w2", "b2")
                    else v)
               for kk, v in params.items()}
    ye = _expert_ffn(eparams, xe)                               # (E,C,d)
    ye = constrain(ye)

    # combine: weight each fetched expert output by its gate
    combine = jnp.einsum("nec,nke,nk->nec", disp,
                         assign.astype(x.dtype), gate_vals)     # (N,E,C)
    y = jnp.einsum("nec,ecd->nd", combine, ye)

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * P_e
    f = (assign[:, 0].astype(jnp.float32)).mean(0)              # (E,)
    p = probs.astype(jnp.float32).mean(0)
    aux = E * jnp.sum(f * p)
    return y, aux


# ----------------------------------------------------------------------
# The dropless top-1 layer of a chip that holds some of the experts
# ----------------------------------------------------------------------
def _rms(x, gain, eps=1e-5):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def zaya_router(h, r_prev, w_in, carry, norm_gain, w1, w2, w_out):
    """The ZAYA router on tokens ``h`` (N, d), in float32 whatever the
    model's dtype: ``r = h W_in + carry * r_prev`` (the state the next
    layer's router receives; ``r_prev`` None for the first layer), then
    ``softmax(W_out gelu(W2 gelu(W1 RMSNorm(r))))`` over ALL experts.
    Weights are (out, in).  Returns ``(r, probabilities (N, E))``."""
    f32 = jnp.float32
    # float32 products, not the TPU's one bfloat16 pass over float32
    # operands: a score's last bits decide which expert a token gets
    mm = lambda a, w: jnp.einsum("nd,rd->nr", a, w.astype(f32),
                                 precision=lax.Precision.HIGHEST)
    r = mm(h.astype(f32), w_in)
    if r_prev is not None:
        r = r + carry.astype(f32) * r_prev.astype(f32)
    u = _rms(r, norm_gain.astype(f32))
    u = jax.nn.gelu(mm(u, w1), approximate=False)
    u = jax.nn.gelu(mm(u, w2), approximate=False)
    return r, jax.nn.softmax(mm(u, w_out), axis=-1)


_RAGGED_OUT_IN = lax.RaggedDotDimensionNumbers(
    # (rows, in) x (group, out, in) -> (rows, out): the stacks keep
    # FullyConnected's (out, in) layout
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def linear_router(h, w, k):
    """The plain router on tokens ``h`` (N, d), float32 whatever the
    model's dtype: ``softmax(h W^T)`` over ALL experts (``w`` (E, d)),
    the ``k`` best of a token (ties: the lower index) and their weights
    normalised over all ``k``.  Returns ``(experts (N, k) int32, weights
    (N, k) float32)``; the gradient reaches ``w`` through the weights."""
    f32 = jnp.float32
    prob = jax.nn.softmax(
        jnp.einsum("nd,ed->ne", h.astype(f32), w.astype(f32),
                   precision=lax.Precision.HIGHEST), axis=-1)
    top, e = lax.top_k(prob, int(k))
    return e.astype(jnp.int32), top / jnp.sum(top, -1, keepdims=True)


def sigmoid_router(h, w, bias, k, scale=1.0):
    """The router with independent scores and a selection bias on tokens
    ``h`` (N, d), float32 whatever the model's dtype: ``s = sigmoid(h
    W^T)`` over ALL experts (``w`` (E, d)); the ``k`` experts with the
    largest ``s + bias`` (``bias`` (E,): a buffer that steers the choice
    and nothing else; ties: the lower index); their weights are ``s``
    WITHOUT the bias at the chosen, over their sum, times ``scale``.
    Returns ``(experts (N, k) int32, weights (N, k) float32)``; the
    gradient reaches ``w`` through the weights and never ``bias``."""
    f32 = jnp.float32
    score = jax.nn.sigmoid(
        jnp.einsum("nd,ed->ne", h.astype(f32), w.astype(f32),
                   precision=lax.Precision.HIGHEST))
    _, e = lax.top_k(lax.stop_gradient(score + bias.astype(f32)), int(k))
    top = jnp.take_along_axis(score, e, axis=-1)
    weights = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * scale
    return e.astype(jnp.int32), weights


def gated_ffn(x, w_gate, w_up, w_down):
    """The dense SiLU-gated FFN ``down(silu(gate x) * up x)`` on rows
    ``x`` (..., d); weights (out, in), no bias; the gate's product in
    float32, rounded once."""
    f32 = jnp.float32
    g = jnp.einsum("...d,fd->...f", x, w_gate, preferred_element_type=f32)
    u = jnp.einsum("...d,fd->...f", x, w_up, preferred_element_type=f32)
    return jnp.einsum("...f,df->...d", (jax.nn.silu(g) * u).astype(x.dtype),
                      w_down)


def _gmm_tiling(rows, groups, k, n):
    """Rows, contraction, columns of a tile of the Pallas grouped matmul
    (jax's megablox), from the shapes it is given.  A tile that straddles
    a group's end is visited once for each group in it, so its rows
    follow the rows a group gets when the buffer is shared evenly: the
    power of two below that, within 128 (the MXU's edge) and 512.
    Contraction and columns take 1024 where the width has them.  At 8192
    rows over 8 groups of width 2048 this is (512, 1024, 1024), the
    faster of the two tried on the v5e (PERF.md, PR 26); 32 groups of
    width 512 get 256-row tiles (PR 30)."""
    per_group = max(1, rows // max(1, groups))
    tm = min(512, max(128, 1 << (per_group.bit_length() - 1)))
    return (min(tm, rows), min(1024, k), min(1024, n))


def _grouped_matmul_impl(rows, dtype, groups=1):
    """How :func:`grouped_matmul` runs when not told: the Pallas kernel
    (``"compiled"``) in a one-device TPU program whose rows fill whole
    tiles, else XLA's ragged product (False; the fallback is counted in
    ``pallas_fallbacks{reason}``).  No knob: a test passes ``impl``."""
    from ..pallas.dispatch import _compiles_here, choose_impl
    here, why, reason = _compiles_here()
    tm = _gmm_tiling(rows, groups, 1, 1)[0]
    supported = (here and rows % tm == 0
                 and dtype in (jnp.bfloat16, jnp.float32))
    return choose_impl(
        "grouped_matmul (no knob)", "auto", "grouped_matmul", supported,
        why=f"{why or 'one TPU device'}, rows={rows}, dtype={dtype}; need "
            f"a one-device TPU program, rows%{tm}==0, bf16/f32",
        fallback_reason=reason or "grouped-geometry")


def grouped_matmul(x, w, group_sizes, impl=None):
    """Rows of ``x`` (N, in), sorted by group, times their group's
    matrix of ``w`` (G, out, in) -> (N, out).  Rows past the last group
    belong to no expert held here: what comes out of them is NOT
    defined (XLA's ragged product writes zeros forward and garbage
    backward, the kernel the reverse), so the caller masks them.

    ``impl``: None chooses (:func:`_grouped_matmul_impl`);
    ``"compiled"`` / ``"interpret"`` is the Pallas grouped matmul under
    scope ``pallas.grouped_matmul`` (forward, and both backward
    products: its own VJP) at the tiles :func:`_gmm_tiling` derives from
    the shapes; False is ``jax.lax.ragged_dot_general``."""
    if impl is None:
        impl = _grouped_matmul_impl(x.shape[0], x.dtype, w.shape[0])
    if not impl:
        return lax.ragged_dot_general(x, w, group_sizes, _RAGGED_OUT_IN)
    from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox
    from ..pallas.attention import _count_launch
    _count_launch("grouped_matmul")
    tiling = _gmm_tiling(x.shape[0], w.shape[0], x.shape[1], w.shape[1])
    with jax.named_scope("pallas.grouped_matmul"):
        return _megablox.gmm(x, w, group_sizes,
                             preferred_element_type=x.dtype, tiling=tiling,
                             transpose_rhs=True,
                             interpret=impl == "interpret")


def _grouped_matmul_back(x, w, group_sizes, grad, impl):
    """The two other directions of ``grouped_matmul(x, w, group_sizes,
    impl)`` for its cotangent ``grad`` (N, out), each called once:
    ``(grad``'s rows times their group's matrix (N, in), ``x^T grad`` a
    group (G, out, in)``)``.  ``jax.vjp`` of the product would run the
    product first; these are the calls megablox's VJP makes after it
    (same tiles, same transposition of the stack's gradient), and the
    transposes of XLA's ragged product where ``impl`` is False.  The
    rows of no group come out undefined, as forward."""
    if not impl:
        product = lambda x, w: lax.ragged_dot_general(
            x, w, group_sizes, _RAGGED_OUT_IN)
        return (jax.linear_transpose(lambda t: product(t, w), x)(grad)[0],
                jax.linear_transpose(lambda t: product(x, t), w)(grad)[0])
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend
    tiling = _gmm_tiling(x.shape[0], w.shape[0], x.shape[1], w.shape[1])
    with jax.named_scope("pallas.grouped_matmul"):
        dx = backend.gmm(grad, w, group_sizes, x.dtype, tiling,
                         interpret=impl == "interpret")
        dw = backend.tgmm(x.swapaxes(0, 1), grad, group_sizes, w.dtype,
                          tiling, num_actual_groups=w.shape[0],
                          interpret=impl == "interpret")
    return dx, dw.swapaxes(1, 2)


def _token_sum_impl(how, rows, tokens, width, dtype):
    """How the sums over a token's rows run where ``k > 1``, given the
    grouped products' decision ``how``: with the compiled kernels, the
    token-ordered sum of ``pallas/token_sum.py`` where it takes the
    shapes (whole tiles of 256 tokens, row blocks and rows of whole
    lane tiles, bf16 or float32), else XLA's ``scatter-add`` (False;
    counted in ``pallas_fallbacks{reason}``).  A test's ``"interpret"``
    runs it at any whole tiles of tokens."""
    from ..pallas import token_sum as _ts
    from ..pallas.dispatch import _compiles_here, choose_impl
    if how == "interpret":
        return how if tokens % _ts.TOKENS == 0 else False
    ok, why = _ts.supported(rows, tokens, width, dtype)
    return choose_impl(
        "token_sum (no knob)", "auto", "token_sum", how == "compiled" and ok,
        why=f"grouped products {how or 'by XLA'}, {why}; need the compiled "
            "grouped products, tokens%256==0, rows%128==0, width%128==0, "
            "bf16/f32",
        fallback_reason=_compiles_here()[2] or "token-sum-geometry")


def _rows(x, at):
    """``x[at]`` along the first axis for indices that lie inside it by
    construction (a permutation, a pair's token): XLA's own gather, with
    no fill of the wide rows."""
    return jnp.take(x, at, axis=0, mode="clip")


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _summed(c, v, key, perm, sorted_key, tokens, out_dtype, how):
    """``out[n] = sum over rows r with key[r] == n of c[r] * v[r]``
    (``c`` None: 1), float32 sums rounded once to ``out_dtype``, by the
    token-ordered sum: ``perm`` sorts ``key`` into ``sorted_key``, the
    rows are permuted once, and a tile of 256 tokens then owns a run of
    them (``pallas/token_sum.py``).  A row keyed ``tokens`` adds
    nothing.  The other direction is what XLA derives from the
    ``scatter-add`` this stands for: the gather ``dy[key] * c`` and the
    row dot for ``c``."""
    from ..pallas.token_sum import token_sum
    return token_sum(
        _rows(v, perm), sorted_key, None if c is None else _rows(c, perm),
        tokens=tokens, out_dtype=out_dtype, interpret=how == "interpret")


def _summed_fwd(c, v, key, perm, sorted_key, tokens, out_dtype, how):
    return (_summed(c, v, key, perm, sorted_key, tokens, out_dtype, how),
            (c, v, key))


def _summed_bwd(tokens, out_dtype, how, kept, dy):
    c, v, key = kept
    f32 = jnp.float32
    got = jnp.where((key < tokens)[:, None],
                    _rows(dy, jnp.minimum(key, tokens - 1)).astype(f32), 0)
    if c is None:
        return None, got.astype(v.dtype), None, None, None
    return (jnp.sum(got * v.astype(f32), axis=-1),
            (got * c[:, None]).astype(v.dtype), None, None, None)


_summed.defvjp(_summed_fwd, _summed_bwd)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _taken(x, token, key, perm, sorted_key, how):
    """``x[token]``, whose gradient is the token-ordered sum of the
    rows' cotangents (:func:`_summed` with no weight) in place of the
    ``scatter-add`` XLA derives."""
    return _rows(x, token)


def _taken_fwd(x, token, key, perm, sorted_key, how):
    return _rows(x, token), (key, perm, sorted_key, x.shape[0])


def _taken_bwd(how, kept, dxs):
    key, perm, sorted_key, tokens = kept
    return (_summed(None, dxs, key, perm, sorted_key, tokens, dxs.dtype, how),
            None, None, None, None)


_taken.defvjp(_taken_fwd, _taken_bwd)


def _row_buckets(tokens, k, held, num_experts, slack=1.25):
    """The static sizes the sorted rows' buffer may take: the expected
    and the worst case.  Nothing is dropped whatever the routing, so
    the larger is a token's every choice held here, ``tokens * min(k,
    held)``.  But a gather and a sum over the tokens of that many rows
    (the combine: the token-ordered sum of ``pallas/token_sum.py``
    beside the products' kernels, else a scatter-add) would cost more
    than the experts when a sixteenth of them is real, so a step whose
    real count fits takes the smaller: one row a token, or ``slack``
    (five quarters) of the expected count ``tokens * k * held /
    num_experts`` where that is larger (a size AT the expected count
    would send every other step to the worst case; a model whose rows
    choose ALIKE, such as a diffusion pass's masked rows, says a larger
    ``slack``: its count moves by whole shares of the rows with which
    experts they favour).  Top-1 has the one size.  With two, the
    smaller is also what the layer keeps for its backward pass (two
    arrays of that many rows, ``hidden`` wide); a step that takes the
    larger keeps nothing and computes a slab again on the way back."""
    worst = tokens * min(k, held)
    expected = -(-tokens * k * held // num_experts)
    size = min(worst, max(tokens, int(math.ceil(slack * expected))))
    return [size] if size == worst else [size, worst]


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def dropless_topk_experts(x, experts, weights, w_gate, w_up, w_down,
                          num_experts, held_first=0, impl=None, act="silu",
                          slack=1.25):
    """Gated expert FFNs for the experts held here (``act`` the gate's
    activation, ``silu`` or ``relu``), ``k`` choices a
    token, nothing dropped.  ``x`` (N, d) tokens; ``experts`` (N, k)
    int32 among ALL ``num_experts`` and ``weights`` (N, k) float32, a
    token's choices and what each counts; ``w_gate``/``w_up`` (held,
    hidden, d), ``w_down`` (held, d, hidden) are experts ``held_first ..
    held_first + held - 1``.  Returns ``(y (N, d), (token, choice) pairs
    an expert int32 (num_experts,))`` with ``y = sum over a token's
    choices e held here of w_e * down_e(act(gate_e x) * up_e x)``; a
    choice whose expert is elsewhere adds 0.  The gradient reaches the
    router through ``weights``.

    The pairs held here are sorted by expert and run through three
    grouped products; with ``k > 1`` the weighted rows of a token are
    then added in float32 and rounded once (``moe.combine``), and the
    gradient of the gather that fetched them (``moe.dispatch``) is the
    same sum with no weight: beside the products' kernels both run as
    the token-ordered sum (:func:`_token_sum_impl`: the held pairs
    sorted by token once, the rows permuted, a one-hot product a tile
    of 256 tokens), else as XLA's ``scatter-add``.  Their buffer has
    the smaller of
    :func:`_row_buckets`'s static sizes (``slack`` times the expected
    count); a step whose count passes it
    (``lax.switch``) runs its pairs a slab of that size at a time,
    forward and backward, so the worst case holds one slab's
    intermediates.  With two arms the layer's gradient is its own
    (``jax.custom_vjp``; differentiating through a switch would keep
    every branch's intermediates).  The small arm, which every step
    near the expected count takes, keeps its gate and up products after
    their mask (``(rows, hidden)`` in ``x``'s dtype; nothing ``d`` wide)
    with the pairs' token order, and goes back from them by hand: the
    six other directions of the three products, each once, the gather
    of ``x`` and the gate's elementwise product again, and no forward
    product a second time.  The worst arm keeps nothing and computes
    each slab again before it goes back through it.  The routing
    (``order``, ``sizes``, ``real``) enters that function as arguments.
    ``impl`` is :func:`grouped_matmul`'s (None: chosen once for all
    three products, and with them for the sums)."""
    N, d = x.shape
    k = experts.shape[1]
    held = w_gate.shape[0]
    E, first = int(num_experts), int(held_first)
    if act not in _GATES:
        raise ValueError("act=%r (one of %s)" % (act, sorted(_GATES)))
    gate = _GATES[act]
    f32 = jnp.float32
    with jax.named_scope("moe.dispatch"):
        pairs = experts.reshape(N * k)
        counts = jnp.sum(pairs[:, None] == jnp.arange(E, dtype=jnp.int32),
                         axis=0, dtype=jnp.int32)
        local = pairs - first
        here = (local >= 0) & (local < held)
        # held pairs first, grouped by expert; the others after them
        order = jnp.argsort(jnp.where(here, local, held), stable=True)
        sizes = lax.slice_in_dim(counts, first, first + held)
        real = jnp.sum(sizes)

    buckets = _row_buckets(N, k, held, E, slack)
    rows = buckets[0]       # of the buffer, and of a slab of the worst case
    how = _grouped_matmul_impl(rows, x.dtype, held) if impl is None else impl
    summed = k > 1 and _token_sum_impl(how, rows, N, d, x.dtype)

    def placed(order, sizes, real, start=None):
        """The first ``rows`` sorted pairs, or with ``start`` the
        ``rows`` from there (a slab): each row's pair and token, the
        groups' sizes among them, and the mask of the real rows."""
        if start is None:
            at = lax.slice_in_dim(order, 0, rows)
            here_s = (jnp.arange(rows) < real)[:, None]
        else:
            # the slab may pass the end of the pairs: rows of no
            # group, masked like the others past ``real``
            at = lax.dynamic_slice_in_dim(
                jnp.pad(order, (0, rows)), start, rows)
            ends = jnp.clip(jnp.cumsum(sizes), start, start + rows)
            sizes = jnp.diff(ends, prepend=start)
            here_s = (start + jnp.arange(rows) < real)[:, None]
        return at, at // k if k > 1 else at, sizes, here_s

    def masked(here_s):
        # what a grouped product leaves in the rows of no group is not
        # defined, forward or backward (it may be NaN): every operand
        # and result is masked there, and with it its gradient
        return lambda t: jnp.where(here_s, t, 0)

    def gated(g, u):
        return gate(g.astype(f32)) * u.astype(f32)

    def run(operands, routing, start=None):
        """The layer over the first ``rows`` sorted pairs; with
        ``start``, over the ``rows`` pairs from there (a slab of the
        worst case: its part of the result, float32).  Beside the
        result, what :func:`back` is handed: the gate and up products
        and the held pairs in token order."""
        x, weights, wg, wu, wd = operands
        with jax.named_scope("moe.dispatch"):
            at, token, sizes, here_s = placed(*routing, start)
            own = masked(here_s)
            if summed:
                # the held pairs in token order, once for both sums over
                # a token's rows: the combine, and the gather's gradient
                key = jnp.where(here_s[:, 0], token, N)
                perm = jnp.argsort(key)
                by_token = (key, perm, _rows(key, perm))
                xs = own(_taken(x, token, *by_token, summed))
            else:
                by_token = ()
                xs = own(jnp.take(x, token, axis=0, unique_indices=k == 1))
        with jax.named_scope("moe.experts"):
            g = own(grouped_matmul(xs, wg, sizes, how))
            u = own(grouped_matmul(xs, wu, sizes, how))
            mid = own(gated(g, u).astype(x.dtype))
            ys = own(grouped_matmul(mid, wd, sizes, how))
        with jax.named_scope("moe.combine"):
            w = jnp.take(weights.reshape(N * k), at)
            out = x.dtype if start is None else f32
            if summed:
                y = _summed(w, ys, *by_token, N, out, summed)
            else:
                ys = ys.astype(f32) * w[:, None]
                if k == 1:  # every token has its one row
                    y = jnp.zeros((N, d), x.dtype).at[token].set(
                        ys.astype(x.dtype), unique_indices=True)
                else:
                    y = jnp.zeros((N, d), f32).at[token].add(ys).astype(out)
        return y, (g, u) + by_token

    def back(operands, routing, kept, dy):
        """:func:`run`'s gradients over the first ``rows`` pairs from
        what it kept, each grouped product's two other directions called
        once (:func:`_grouped_matmul_back`): of the forward only the
        gather of ``x`` and the gate's elementwise product run again.
        The down product's input gradient is taken WITHOUT the pair's
        weight ``c``, ``t = got w_down``: then ``dc = sum_f t * mid`` (a
        row dot over ``hidden`` columns that needs no forward down
        product), ``dmid = c * t``, and the weight rides the narrow side
        of the stack's gradient, ``(c * mid)^T got``."""
        x, weights, wg, wu, wd = operands
        g, u, *by_token = kept
        with jax.named_scope("moe.combine"):
            at, token, sizes, here_s = placed(*routing)
            own = masked(here_s)
            c = jnp.take(weights.reshape(N * k), at)[:, None]
            got = own(_rows(dy, token))
        with jax.named_scope("moe.experts"):
            mid, gated_back = jax.vjp(gated, g, u)
            t, dwd = _grouped_matmul_back(
                (c * mid).astype(x.dtype), wd, sizes, got, how)
            t = own(t).astype(f32)
            dc = jnp.sum(t * mid.astype(x.dtype).astype(f32), axis=-1)
            dg, du = gated_back(c * t)
        with jax.named_scope("moe.dispatch"):
            xs = own(_rows(x, token))
        with jax.named_scope("moe.experts"):
            dxg, dwg = _grouped_matmul_back(xs, wg, sizes, dg, how)
            dxu, dwu = _grouped_matmul_back(xs, wu, sizes, du, how)
        with jax.named_scope("moe.dispatch"):
            dxs = own(dxg + dxu)
            dx = _summed(None, dxs, *by_token, N, x.dtype, summed) \
                if summed else jnp.zeros((N, d), x.dtype).at[token].add(dxs)
        dw = jnp.zeros(N * k, f32).at[at].add(dc).reshape(N, k)
        return dx, dw.astype(weights.dtype), dwg, dwu, dwd

    operands = (x, weights, w_gate, w_up, w_down)
    if len(buckets) == 1:
        return run(operands, (order, sizes, real))[0], counts

    # a step whose pairs pass the smaller size runs them a slab of that
    # size at a time, as many slabs as hold the real pairs: the worst
    # case costs what its pairs cost, and holds one slab's intermediates
    def slab(j, routing, *operands):
        return run(operands, routing, j * rows)[0]

    def slabs(routing):
        _, _, real = routing
        return (real + rows - 1) // rows

    def worst(operands, routing):
        return lax.fori_loop(
            0, slabs(routing), lambda j, y: y + slab(j, routing, *operands),
            jnp.zeros((N, d), f32)).astype(x.dtype)

    def worst_back(operands, routing, kept, dy):
        def more(j, acc):
            got = jax.vjp(_functools.partial(slab, j, routing), *operands)[1](
                dy.astype(f32))
            return tuple(a + g.astype(f32) for a, g in zip(acc, got))

        acc = lax.fori_loop(0, slabs(routing), more, tuple(
            jnp.zeros(o.shape, f32) for o in operands))
        return tuple(a.astype(o.dtype) for a, o in zip(acc, operands))

    def arm(routing):
        _, _, real = routing
        return (real > rows).astype(jnp.int32)

    @jax.custom_vjp
    def sized(operands, routing):
        return lax.switch(arm(routing), [lambda *a: run(*a)[0], worst],
                          operands, routing)

    def sized_fwd(operands, routing):
        # the small arm hands its backward what ``run`` keeps; the worst
        # arm keeps nothing (zeros of those shapes) and runs each slab
        # again on the way back
        nothing = (jnp.zeros((rows, w_gate.shape[1]), x.dtype),) * 2 \
            + (jnp.zeros(rows, jnp.int32),) * (3 if summed else 0)
        y, kept = lax.switch(
            arm(routing), [run, lambda *a: (worst(*a), nothing)],
            operands, routing)
        return y, (operands, routing, kept)

    def sized_bwd(res, dy):
        operands, routing, kept = res
        return lax.switch(arm(routing), [back, worst_back],
                          operands, routing, kept, dy), None

    sized.defvjp(sized_fwd, sized_bwd)
    return sized(operands, (order, sizes, real)), counts


def dropless_top1_experts(x, prob, w_gate, w_up, w_down, held_first=0,
                          impl=None):
    """:func:`dropless_topk_experts` at ``k = 1`` from probabilities:
    ``prob`` (N, E) float32 over ALL experts; a token goes to ``e =
    argmax(prob)`` (ties: the lower index) with weight ``p_e``, so
    ``y = p_e * down_e(silu(gate_e x) * up_e x)`` where ``e`` is held
    here and 0 otherwise.  Returns ``(y (N, d), tokens an expert int32
    (E,))``."""
    with jax.named_scope("moe.dispatch"):
        e = jnp.argmax(prob, axis=-1).astype(jnp.int32)[:, None]
        pe = jnp.take_along_axis(prob, e, axis=-1)
    return dropless_topk_experts(x, e, pe, w_gate, w_up, w_down,
                                 prob.shape[-1], held_first, impl)
