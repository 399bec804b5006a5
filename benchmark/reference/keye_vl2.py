"""Plain reference of Keye-VL-2.0's language model (Kwai): grouped-query
attention over the keys that a learned index scorer picks for each
query (DeepSeek Sparse Attention), then a top-k expert sublayer behind a
softmax router, in every layer, as ISSUE 38 section 1 writes the layer
equations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel, no bisection: a query's chosen keys are those at
or above its ``topk``-th largest score as ``lax.top_k`` gives it (a tie
at that value going to the lower key, by a running count), the (S, S)
scores of scorer and heads are materialised, a block of query rows at a
time under ``jax.checkpoint`` so that three float32 steps at 16 384
tokens fit on one chip (a block changes the order of no sum); every held
expert runs over every token and a mask keeps the (token, choice) pairs
routed to it.  It imports nothing of ``mxnet_tpu`` and takes nothing the
program made: parameters come from :func:`init_leaf`, by the names
``models/keye_vl2.py`` uses.

Two objectives.  The scorer reads ``stop_gradient`` of the normalised
stream and the choice is a mask, so the cross-entropy never reaches the
scorer's leaves (``layerN_attn_idx_*``); the index loss ``L^I``, the
mean over tokens of ``KL(p_t || softmax over the chosen of I[t])`` with
``p_t`` the heads' mean probabilities behind ``stop_gradient``, reaches
them and nothing else.  :func:`loss` returns the CROSS-ENTROPY as its
value (what the ``ce`` metric reads) and the gradient of ``ce + L^I``.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts and keeps the ``top_k`` best with weights
normalised over all ``top_k``; ``experts_held = [first, count]`` says
which experts live here; a (token, choice) whose expert is elsewhere
adds 0, here as in the program.  The vocabulary is the slice
``num_classes``.

Departures from the source (the configuration's ``assumed`` says each
as a sentence): no vision tower (on text the three multimodal position
ids agree: plain rotary), no dense warm-up stage of the scorer, no
balancing loss.

``precision`` selects the arithmetic of the matmul operands of the main
attention's projections and products, the expert FFNs and the head:
``"f32"`` is the reference, ``"fp8"`` the control (``gpt2.mm_fp8``).
The router and the whole scorer are float32 in the control too: the
architecture says so.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.zaya import (data_shapes, device_batch, leaf_key,  # noqa: F401 (the interface)
                            make_batch, rotary)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "keye_vl2.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'keye_vl2' (mxnet_tpu/models/keye_vl2.py): "
                     "the cell cannot run here")

RMS_EPS = 1e-6
INIT_STD = 0.02
EMBED_STD = 1.0
Q_BLOCK = 256               # query rows a block of attention
ROW_BLOCK = 1024            # rows a block of the head


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def dims(cfg):
    """The sizes of ``kwargs`` as a dict of ints (and one float)."""
    held = cfg.get("experts_held")
    E = int(cfg["num_experts"])
    if held is None:
        held = (0, E)
    elif isinstance(held, int):
        held = (0, held)
    return {
        "V": int(cfg["num_classes"]), "L": int(cfg["num_layers"]),
        "d": int(cfg["d_model"]), "Hq": int(cfg["q_heads"]),
        "Hk": int(cfg["kv_heads"]), "D": int(cfg["head_dim"]),
        "theta": float(cfg.get("rope_theta", 1e7)),
        "Hi": int(cfg["idx_heads"]), "Di": int(cfg["idx_dim"]),
        "K": int(cfg["topk"]), "F": int(cfg["expert_dim"]), "E": E,
        "k": int(cfg["top_k"]), "first": int(held[0]), "held": int(held[1]),
        "S": int(cfg["seq_len"]),
    }


def layer_specs(cfg, i):
    z = dims(cfg)
    d, Hq, Hk, D, Hi, Di = (z[n] for n in ("d", "Hq", "Hk", "D", "Hi", "Di"))
    p = "layer%s_" % i
    return [
        (p + "in_norm_gamma", (d,)),
        (p + "attn_q_weight", (Hq * D, d)),
        (p + "attn_k_weight", (Hk * D, d)),
        (p + "attn_v_weight", (Hk * D, d)),
        (p + "attn_q_norm_gamma", (D,)),
        (p + "attn_k_norm_gamma", (D,)),
        (p + "attn_o_weight", (d, Hq * D)),
        (p + "attn_idx_q_weight", (Hi * Di, d)),
        (p + "attn_idx_k_weight", (Di, d)),
        (p + "attn_idx_w_weight", (Hi, d)),
        (p + "attn_idx_k_norm_gamma", (Di,)),
        (p + "attn_idx_k_norm_beta", (Di,)),
        (p + "post_norm_gamma", (d,)),
        (p + "moe_gate_weight", (z["held"], z["F"], d)),
        (p + "moe_up_weight", (z["held"], z["F"], d)),
        (p + "moe_down_weight", (z["held"], d, z["F"])),
        (p + "moe_router_weight", (z["E"], d)),
    ]


def param_specs(cfg):
    """[(name, shape)] of every parameter, in checkpoint order."""
    z = dims(cfg)
    out = [("tok_embed_weight", (z["V"], z["d"]))]
    for i in range(z["L"]):
        out += layer_specs(cfg, i)
    return out + [("final_norm_gamma", (z["d"],)),
                  ("lm_head_weight", (z["V"], z["d"]))]


def is_scorer(name):
    """Whether a leaf belongs to an index scorer (trained by ``L^I``)."""
    return "_attn_idx_" in name


def leaf_kind(name):
    """How a parameter is initialised, by its name (the configuration's
    ``assumed.init``): the embedding normal(0, 1); the norm gains 1 and
    the scorer's LayerNorm shift 0; every other weight normal(0, 0.02)."""
    if name.endswith("_gamma"):
        return "ones"
    if name.endswith("_beta"):
        return "zeros"
    if name == "tok_embed_weight":
        return "embed"
    return "normal"


def leaf_value(k, kind, shape):
    """A parameter from ITS key, float32 (weights exact in bfloat16:
    the caller casts to the type its side holds)."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    std = EMBED_STD if kind == "embed" else INIT_STD
    w = jax.random.normal(k, shape, jnp.float32) * std
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def rms_norm(x, w):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(ms + RMS_EPS) * w


def choose(scores, causal, topk):
    """The chosen (rows, S) bool of ``scores`` (rows, S): per row the
    ``topk`` largest among ``causal``, a tie going to the lower column;
    every causal column of a row that has no more than ``topk``."""
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = lax.top_k(masked, min(topk, scores.shape[-1]))[0][..., -1:]
    above = masked > kth
    ties = causal & (masked == kth)
    room = topk - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (ties & (jnp.cumsum(ties, -1) <= room)))


def scorer(h, p, pre, z):
    """The index scorer's operands from the normalised stream (B, S, d),
    float32: queries (B, S, Hi, Di), the shared key (B, S, Di), and the
    head weights (B, S, Hi) with the two constant scales in them."""
    B, S, _ = h.shape
    Hi, Di = z["Hi"], z["Di"]
    q = _einsum("bsd,ed->bse", h, p[pre + "attn_idx_q_weight"]) \
        .reshape(B, S, Hi, Di)
    k = layer_norm(_einsum("bsd,ed->bse", h, p[pre + "attn_idx_k_weight"]),
                       p[pre + "attn_idx_k_norm_gamma"],
                       p[pre + "attn_idx_k_norm_beta"])
    w = _einsum("bsd,hd->bsh", h, p[pre + "attn_idx_w_weight"]) \
        * (Hi ** -0.5 * Di ** -0.5)
    return (rotary(q, Di, z["theta"]),
            rotary(k[:, :, None, :], Di, z["theta"])[:, :, 0], w)


def layer_norm(x, gamma, beta):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + RMS_EPS) * gamma + beta


def sparse_attention(q, k, v, qi, ki, wi, z, precision, blk=None,
                     want_chosen=False):
    """``(o (B, S, Hq, D), sum over tokens of the index loss, chosen
    pairs (B, S, S) bool or None)``: every query's softmax over its
    chosen keys, a block of query rows against all S keys at a time."""
    B, S, Hq, D = q.shape
    Hk, R = z["Hk"], z["Hq"] // z["Hk"]
    blk = min(Q_BLOCK, S) if blk is None else blk
    while S % blk:
        blk -= 1

    @jax.checkpoint
    def rows(qb, qib, wib, start):
        causal = (start + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        zi = _einsum("bqhe,bke->bhqk", qib, ki)
        score = jnp.sum(wib.transpose(0, 2, 1)[..., None] * jax.nn.relu(zi), 1)
        chosen = choose(score, causal[None], z["K"])          # (B, blk, S)
        on = chosen[:, None, None]
        s = _mm("bqgre,bkge->bgrqk", qb.reshape(B, blk, Hk, R, D), k,
                precision) * D ** -0.5
        a = jnp.where(on, jax.nn.softmax(jnp.where(on, s, -1e30), -1), 0.0)
        o = _mm("bgrqk,bkge->bqgre", a, v, precision)
        # the scorer's objective: the heads' mean probabilities, as data
        target = lax.stop_gradient(jnp.mean(a, axis=(1, 2)))
        logq = jax.nn.log_softmax(jnp.where(chosen, score, -1e30), -1)
        kl = jnp.where(chosen & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - logq), 0.0)
        return (o.reshape(B, blk, Hq, D), jnp.sum(kl),
                chosen if want_chosen else None)

    cut = lambda x: x.reshape((B, S // blk, blk) + x.shape[2:]).swapaxes(0, 1)
    o, kl, chosen = lax.map(lambda a: rows(*a), (
        cut(q), cut(qi), cut(wi), jnp.arange(S // blk) * blk))
    if want_chosen:
        chosen = chosen.swapaxes(0, 1).reshape(B, S, S)
    return o.swapaxes(0, 1).reshape(B, S, Hq, D), jnp.sum(kl), chosen


def attention_sublayer(h, p, pre, z, precision, blk=None,
                       want_chosen=False):
    """The attention sublayer on the normalised stream (B, S, d):
    ``(result (B, S, d), this layer's index loss summed over tokens,
    chosen pairs)``."""
    B, S, _ = h.shape
    Hq, Hk, D = z["Hq"], z["Hk"], z["D"]
    q = _mm("bsd,ed->bse", h, p[pre + "attn_q_weight"], precision) \
        .reshape(B, S, Hq, D)
    k = _mm("bsd,ed->bse", h, p[pre + "attn_k_weight"], precision) \
        .reshape(B, S, Hk, D)
    v = _mm("bsd,ed->bse", h, p[pre + "attn_v_weight"], precision) \
        .reshape(B, S, Hk, D)
    q = rotary(rms_norm(q, p[pre + "attn_q_norm_gamma"]), D, z["theta"])
    k = rotary(rms_norm(k, p[pre + "attn_k_norm_gamma"]), D, z["theta"])
    qi, ki, wi = scorer(lax.stop_gradient(h), p, pre, z)
    o, kl, chosen = sparse_attention(q, k, v, qi, ki, wi, z, precision, blk,
                                     want_chosen)
    return _mm("bse,de->bsd", o.reshape(B, S, Hq * D),
               p[pre + "attn_o_weight"], precision), kl, chosen


def gated_ffn(h, wg, wu, wd, precision):
    g = _mm("nd,fd->nf", h, wg, precision)
    u = _mm("nd,fd->nf", h, wu, precision)
    return _mm("nf,df->nd", jax.nn.silu(g) * u, wd, precision)


def route(h, p, pre, z):
    """The ``top_k`` experts of every token (N, k) and their weights,
    normalised over all ``top_k``; float32 always.  ``lax.top_k`` gives
    the lower index first among equals."""
    prob = jax.nn.softmax(_einsum("nd,ed->ne", h, p[pre + "moe_router_weight"]),
                          axis=-1)
    w, e = lax.top_k(prob, z["k"])
    return e, w / jnp.sum(w, -1, keepdims=True)


def experts(h, p, pre, z, precision):
    """The expert sublayer on normalised tokens (N, d): ``(the part of
    the experts held here, chosen experts (N, k))``.  Every held expert
    runs over every token; the mask keeps its own pairs."""
    e, w = route(h, p, pre, z)

    def one(y, xs):
        idx, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(e == idx, w, 0.0), -1, keepdims=True)
        return y + mine * gated_ffn(h, wg, wu, wd, precision), None

    ids = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                    (ids, p[pre + "moe_gate_weight"],
                     p[pre + "moe_up_weight"], p[pre + "moe_down_weight"]))
    return y, e


def block(x, p, i, z, precision="f32"):
    """Layer ``i`` on (B, S, d): ``(x, the layer's index loss summed
    over tokens)``."""
    B, S, d = x.shape
    pre = "layer%d_" % i
    h = rms_norm(x, p[pre + "in_norm_gamma"])
    a, kl, _ = attention_sublayer(h, p, pre, z, precision)
    x = x + a
    h = rms_norm(x, p[pre + "post_norm_gamma"]).reshape(B * S, d)
    y, _ = experts(h, p, pre, z, precision)
    return x + y.reshape(B, S, d), kl


def head_loss(x, labels, p, precision):
    """Summed next-token cross-entropy of (N, d) rows against the untied
    head over the vocabulary slice, a block of rows at a time."""
    N = x.shape[0]
    blk = min(ROW_BLOCK, N)
    while N % blk:
        blk -= 1

    @jax.checkpoint
    def rows(xb, lb):
        logits = _mm("nd,vd->nv", rms_norm(xb, p["final_norm_gamma"]),
                     p["lm_head_weight"], precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    parts = lax.map(lambda a: rows(*a), (x.reshape(N // blk, blk, -1),
                                         labels.reshape(N // blk, blk)))
    return jnp.sum(parts)


def forward(params, tokens, cfg, precision="f32"):
    """The residual stream after the last layer (B, S, d) and the sum
    over layers of the index loss (each a mean over tokens)."""
    z = dims(cfg)
    x = params["tok_embed_weight"][tokens]
    index_loss = 0.0
    for i in range(z["L"]):
        x, kl = jax.checkpoint(
            lambda x, p, i=i: block(x, p, i, z, precision))(x, params)
        index_loss = index_loss + kl / tokens.size
    return x, index_loss


def losses(params, tokens, labels, cfg, precision="f32"):
    """``(ce, L^I)``: the mean next-token cross-entropy and the summed
    index loss, each with its own gradient paths."""
    x, index_loss = forward(params, tokens, cfg, precision)
    n = tokens.size
    ce = head_loss(x.reshape(n, -1), labels.reshape(n), params, precision) / n
    return ce, index_loss


def loss(params, aux, tokens, labels, cfg, precision="f32"):
    """The value is the mean cross-entropy (what the repo's ``ce``
    metric reads); the gradient is that of ``ce + L^I``, the step's
    objective: ``L^I`` joins at value 0."""
    ce, index_loss = losses(params, tokens, labels, cfg, precision)
    return ce + (index_loss - lax.stop_gradient(index_loss)), aux


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def pairs(cfg):
    """``(causal, chosen)`` (query, key) pairs of one sequence: every
    s <= t, and min(t + 1, topk) of them a query."""
    z = dims(cfg)
    S, K = z["S"], min(z["K"], z["S"])
    return S * (S + 1) // 2, K * (K + 1) // 2 + (S - K) * K


def dsa_attention_flops(cfg):
    """FLOPs of the sparse cores a training step of one sequence needs,
    all layers, the CHOSEN pairs only, no recompute: per pair and head
    QK^T and PV forward; dV, dP, dQ, dK backward."""
    z = dims(cfg)
    return z["L"] * 2 * pairs(cfg)[1] * z["Hq"] * 6 * z["D"]


def dsa_attention_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the sparse
    cores, all layers: one read of q, k, v and one write of o (the
    model's dtype), and as much again for their gradients."""
    z = dims(cfg)
    return z["L"] * 2 * z["S"] * z["D"] * (2 * z["Hq"] + 2 * z["Hk"]) \
        * bytes_per_value


def dsa_indexer_flops(cfg):
    """FLOPs of the index scorer's S x S work a training step of one
    sequence needs, all layers, no recompute: the heads' products over
    EVERY causal pair forward (the choice needs them all); over the
    chosen pairs backward, for the queries and for the key."""
    z = dims(cfg)
    causal, chosen = pairs(cfg)
    return z["L"] * 2 * z["Hi"] * z["Di"] * (causal + 2 * chosen)


def dsa_indexer_bytes(cfg, bytes_per_value=4):
    """Bytes the same work has to move: one read of the scorer's
    queries, key and head weights (float32) and as much for their
    gradients, and one write and one read of the choice as bits."""
    z = dims(cfg)
    S = z["S"]
    return z["L"] * (2 * S * (z["Hi"] * z["Di"] + z["Di"] + z["Hi"])
                     * bytes_per_value + 2 * S * S // 8)


def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``top_k * held / num_experts`` of a token's
    choices reach an expert held here.  The sparse cores are counted at
    the CHOSEN pairs, the scorer at every causal pair.  Lookups, norms,
    rotary, the choice itself and other elementwise work are not
    counted."""
    z = dims(cfg)
    S, d, L = z["S"], z["d"], z["L"]
    causal, chosen = pairs(cfg)
    return {
        "projections": L * 2 * S * d * z["D"] * (2 * z["Hq"] + 2 * z["Hk"]),
        "scorer_projections": L * 2 * S * d * (
            z["Hi"] * z["Di"] + z["Di"] + z["Hi"]),
        "scorer": L * 2 * causal * z["Hi"] * z["Di"],
        "attention": L * 2 * chosen * z["Hq"] * 2 * z["D"],
        "router": L * 2 * S * d * z["E"],
        "experts": L * (S * z["k"] * z["held"] / z["E"]) * 3 * 2 * d * z["F"],
        "head": 2 * S * d * z["V"],
    }


def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes of one sequence of the
    configuration's length under EVEN routing, no recompute: twice the
    forward going back, except the scorer's S x S product, which goes
    back over the chosen pairs only (:func:`dsa_indexer_flops`)."""
    f = forward_flops_per_sample(cfg)
    return 3 * (sum(f.values()) - f["scorer"]) + dsa_indexer_flops(cfg)


def expert_product_flops(cfg, tokens_held):
    """FLOPs, forward and backward, of the three grouped products (gate,
    up, down) for ``tokens_held`` (token, choice, layer) triples that
    reached an expert held here."""
    z = dims(cfg)
    return 3 * tokens_held * 3 * 2 * z["d"] * z["F"]
