"""Plain reference of the Kanana-2 decoder (Kakao, ``model_type``
``deepseek_v3``): multi-head latent attention in every layer, a leading
dense SiLU-gated FFN, then top-k expert sublayers behind independent
sigmoid scores with a selection bias and an ungated shared expert, as
ISSUE 32 writes the layer equations from the public
``modeling_deepseek_v3.py``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel, no sort: the (S, S) scores are materialised, a
block of query rows at a time under ``jax.checkpoint`` so that three
float32 steps at 8192 tokens fit on one chip (a block changes the order
of no sum); every held expert runs over every token and a mask keeps
the (token, choice) pairs routed to it.  It imports nothing of
``mxnet_tpu`` and takes nothing the program made: parameters come from
:func:`init_leaf` and the selection bias from :func:`init_aux`, by the
names ``models/kanana2.py`` uses.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts, chooses ``top_k`` by score plus bias, and
weighs them by their scores WITHOUT the bias, normalised over all
``top_k`` and scaled by ``route_scale``; ``experts_held = [first,
count]`` says which experts live here; a (token, choice) whose expert is
elsewhere adds 0, here as in the program.  The shared expert is whole on
every chip.  The vocabulary is the slice ``num_classes``.

Departures from the source (the configuration's ``assumed`` says each
as a sentence): the bias's load-driven update and the balancing loss
are not built; rotary position turns each neighbouring pair of the 64
rotary channels IN PLACE, where the source first moves the even
channels before the odd ones (one permutation of queries and keys
alike: the scores are the same); ``n_group`` = ``topk_group`` = 1, so
the choice among groups is the identity and is not written.

``precision`` selects the arithmetic of the matmul operands of the
projections, attention, the feed-forwards and the head: ``"f32"`` is the
reference, ``"fp8"`` the control (``gpt2.mm_fp8``).  The router and the
latent's norm are float32 in the control too: the architecture says so.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.zaya import (data_shapes, device_batch, leaf_key,  # noqa: F401 (the interface)
                            make_batch)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "kanana2.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'kanana2' (mxnet_tpu/models/kanana2.py): "
                     "the cell cannot run here")

RMS_EPS = 1e-6
INIT_STD = 0.02
EMBED_STD = 1.0
BIAS_STD = 0.005            # the seeded selection bias (init_aux)
BIAS_SEED = 0x6B32          # its own key: a buffer, not a weight of --seed
Q_BLOCK = 1024              # rows a block of attention or of the head


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def dims(cfg):
    """The sizes of ``kwargs`` as a dict of ints (and two floats)."""
    held = cfg.get("experts_held")
    E = int(cfg["num_experts"])
    if held is None:
        held = (0, E)
    elif isinstance(held, int):
        held = (0, held)
    return {
        "V": int(cfg["num_classes"]), "L": int(cfg["num_layers"]),
        "d": int(cfg["d_model"]), "H": int(cfg["heads"]),
        "Dn": int(cfg["nope_dim"]), "Dr": int(cfg["rope_dim"]),
        "Dv": int(cfg["v_dim"]), "C": int(cfg["kv_rank"]),
        "theta": float(cfg.get("rope_theta", 1e6)),
        "dense": int(cfg.get("dense_layers", 1)),
        "Fd": int(cfg["dense_dim"]), "F": int(cfg["expert_dim"]),
        "Fs": int(cfg["shared_dim"]), "E": E, "k": int(cfg["top_k"]),
        "scale": float(cfg.get("route_scale", 1.0)),
        "first": int(held[0]), "held": int(held[1]),
        "S": int(cfg["seq_len"]),
    }


def layer_specs(cfg, i):
    z = dims(cfg)
    d, H, C = z["d"], z["H"], z["C"]
    p = "layer%s_" % i
    out = [
        (p + "in_norm_gamma", (d,)),
        (p + "attn_q_weight", (H * (z["Dn"] + z["Dr"]), d)),
        (p + "attn_kva_weight", (C + z["Dr"], d)),
        (p + "attn_kv_norm_gamma", (C,)),
        (p + "attn_kvb_weight", (H * (z["Dn"] + z["Dv"]), C)),
        (p + "attn_o_weight", (d, H * z["Dv"])),
        (p + "post_norm_gamma", (d,)),
    ]
    if i < z["dense"]:
        return out + [(p + "ffn_gate_weight", (z["Fd"], d)),
                      (p + "ffn_up_weight", (z["Fd"], d)),
                      (p + "ffn_down_weight", (d, z["Fd"]))]
    F, Fs = z["F"], z["Fs"]
    return out + [
        (p + "moe_gate_weight", (z["held"], F, d)),
        (p + "moe_up_weight", (z["held"], F, d)),
        (p + "moe_down_weight", (z["held"], d, F)),
        (p + "moe_router_weight", (z["E"], d)),
        (p + "moe_shared_gate_weight", (Fs, d)),
        (p + "moe_shared_up_weight", (Fs, d)),
        (p + "moe_shared_down_weight", (d, Fs)),
    ]


def param_specs(cfg):
    """[(name, shape)] of every parameter, in checkpoint order."""
    z = dims(cfg)
    out = [("tok_embed_weight", (z["V"], z["d"]))]
    for i in range(z["L"]):
        out += layer_specs(cfg, i)
    return out + [("final_norm_gamma", (z["d"],)),
                  ("lm_head_weight", (z["V"], z["d"]))]


def leaf_kind(name):
    """How a parameter is initialised, by its name (the configuration's
    ``assumed.init``): the embedding normal(0, 1), so that tokens stay
    distinguishable through the mixers and the router spreads them; the
    norm gains 1; every other weight normal(0, 0.02)."""
    if name.endswith("_gamma"):
        return "ones"
    if name == "tok_embed_weight":
        return "embed"
    return "normal"


def leaf_value(k, kind, shape):
    """A parameter from ITS key, float32 (weights exact in bfloat16:
    the caller casts to the type its side holds)."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    std = EMBED_STD if kind == "embed" else INIT_STD
    w = jax.random.normal(k, shape, jnp.float32) * std
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


def init_aux(cfg):
    """{name: the selection bias of an expert layer (num_experts,)
    float32}.  The source starts it at 0 and moves it by the experts'
    load; here it is a fixed normal(0, ``BIAS_STD``) draw a layer, large
    enough to flip some of a token's choices (so that a run shows
    whether the bias joins the choice, and only the choice) and small
    enough to leave the load near even.  The harness's interface gives
    no seed, and a buffer that no step changes needs none."""
    z = dims(cfg)
    base = jax.random.PRNGKey(BIAS_SEED)
    return {"layer%d_moe_router_bias" % i: BIAS_STD * jax.random.normal(
        jax.random.fold_in(base, i), (z["E"],), jnp.float32)
        for i in range(z["dense"], z["L"])}


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def rms_norm(x, w):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(ms + RMS_EPS) * w


def rotary_interleaved(x, theta):
    """Rotary position on all of the last axis of (B, S, H, R), the
    source's interleaved layout: channels 2i and 2i + 1 are a pair."""
    S, R = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(R // 2, dtype=jnp.float32) * 2.0 / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, precision):
    """Causal softmax attention of (B, S, H, D) queries and keys on
    (B, S, H, Dv) values, scale 1/sqrt(D); the (block, S) scores of a
    block of queries at a time."""
    B, S, H, D = q.shape
    blk = min(Q_BLOCK, S)
    while S % blk:
        blk -= 1

    @jax.checkpoint
    def rows(qb, start):
        s = _mm("bqhe,bkhe->bhqk", qb, k, precision) * D ** -0.5
        mask = (start + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        a = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _mm("bhqk,bkhe->bqhe", a, v, precision)

    qb = q.reshape(B, S // blk, blk, H, D).transpose(1, 0, 2, 3, 4)
    out = lax.map(lambda a: rows(*a), (qb, jnp.arange(S // blk) * blk))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def latent_attention(h, p, pre, z, precision):
    """The MLA sublayer on the normalised stream (B, S, d)."""
    B, S, _ = h.shape
    H, Dn, Dr, Dv, C = z["H"], z["Dn"], z["Dr"], z["Dv"], z["C"]
    q = _mm("bsd,ed->bse", h, p[pre + "attn_q_weight"], precision) \
        .reshape(B, S, H, Dn + Dr)
    ckr = _mm("bsd,ed->bse", h, p[pre + "attn_kva_weight"], precision)
    c, k_rope = ckr[..., :C], ckr[..., C:]
    kv = _mm("bsc,ec->bse", rms_norm(c, p[pre + "attn_kv_norm_gamma"]),
             p[pre + "attn_kvb_weight"], precision).reshape(B, S, H, Dn + Dv)
    q = jnp.concatenate(
        [q[..., :Dn], rotary_interleaved(q[..., Dn:], z["theta"])], -1)
    # ONE rotary key a token, shared by all heads
    k_rope = rotary_interleaved(k_rope[:, :, None, :], z["theta"])
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(k_rope, (B, S, H, Dr))], -1)
    o = attention(q, k, kv[..., Dn:], precision)
    return _mm("bse,de->bsd", o.reshape(B, S, H * Dv),
               p[pre + "attn_o_weight"], precision)


def gated_ffn(h, wg, wu, wd, precision):
    g = _mm("...d,fd->...f", h, wg, precision)
    u = _mm("...d,fd->...f", h, wu, precision)
    return _mm("...f,df->...d", jax.nn.silu(g) * u, wd, precision)


def route(h, p, bias, pre, z):
    """The ``top_k`` experts of every token (N, k) and their weights;
    float32 always.  The bias joins the scores for the CHOICE only;
    ``lax.top_k`` gives the lower index first among equals."""
    s = jax.nn.sigmoid(_einsum("nd,ed->ne", h, p[pre + "moe_router_weight"]))
    _, e = lax.top_k(s + bias, z["k"])
    w = jnp.take_along_axis(s, e, axis=-1)
    return e, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * z["scale"]


def experts(h, p, bias, pre, z, precision):
    """The expert sublayer on normalised tokens (N, d): ``(routed part
    of the experts held here, shared expert's part, chosen experts
    (N, k))``; the sublayer's result is the sum of the two parts.  Every
    held expert runs over every token; the mask keeps its own pairs."""
    e, w = route(h, p, bias, pre, z)

    def one(y, xs):
        idx, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(e == idx, w, 0.0), -1, keepdims=True)
        return y + mine * gated_ffn(h, wg, wu, wd, precision), None

    ids = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                    (ids, p[pre + "moe_gate_weight"],
                     p[pre + "moe_up_weight"], p[pre + "moe_down_weight"]))
    s = gated_ffn(h, p[pre + "moe_shared_gate_weight"],
                  p[pre + "moe_shared_up_weight"],
                  p[pre + "moe_shared_down_weight"], precision)
    return y, s, e


def block(x, p, aux, i, z, precision="f32"):
    """Layer ``i`` on (B, S, d): ``(x, chosen experts (B*S, k))``, the
    second None for a dense layer."""
    B, S, d = x.shape
    pre = "layer%d_" % i
    h = rms_norm(x, p[pre + "in_norm_gamma"])
    x = x + latent_attention(h, p, pre, z, precision)
    h = rms_norm(x, p[pre + "post_norm_gamma"])
    if i < z["dense"]:
        return x + gated_ffn(h, p[pre + "ffn_gate_weight"],
                             p[pre + "ffn_up_weight"],
                             p[pre + "ffn_down_weight"], precision), None
    y, s, e = experts(h.reshape(B * S, d), p, aux[pre + "moe_router_bias"],
                      pre, z, precision)
    return x + (y + s).reshape(B, S, d), e


def head_loss(x, labels, p, precision):
    """Summed next-token cross-entropy of (N, d) rows against the untied
    head over the vocabulary slice, a block of rows at a time."""
    N = x.shape[0]
    blk = min(Q_BLOCK, N)
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


def forward(params, aux, tokens, cfg, precision="f32"):
    """The residual stream after the last layer (B, S, d) and the
    experts every expert layer chose for every token (L - dense, B*S,
    k)."""
    z = dims(cfg)
    x = params["tok_embed_weight"][tokens]
    chosen = []
    for i in range(z["L"]):
        x, e = jax.checkpoint(
            lambda x, p, a, i=i: block(x, p, a, i, z, precision))(
                x, params, aux)
        if e is not None:
            chosen.append(e)
    return x, jnp.stack(chosen)


def loss(params, aux, tokens, labels, cfg, precision="f32"):
    """Mean next-token cross-entropy over every position: what the
    repo's ``ce`` metric reads and what SoftmaxOutput with
    ``normalization='batch'`` differentiates.  The bias takes no
    gradient (it reaches the result through ``top_k``'s indices alone)
    and is handed back as it came."""
    x, _ = forward(params, aux, tokens, cfg, precision)
    n = tokens.size
    total = head_loss(x.reshape(n, -1), labels.reshape(n), params, precision)
    return total / n, aux


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``top_k * held / num_experts`` of a token's
    choices reach an expert held here.  Causal attention is counted at
    half the square: QK^T over 192 channels, PV over 128.  Lookups,
    norms, rotary and other elementwise work are not counted."""
    z = dims(cfg)
    S, d, H, L = z["S"], z["d"], z["H"], z["L"]
    D = z["Dn"] + z["Dr"]
    n_moe = L - z["dense"]
    return {
        "mla_projections": L * 2 * S * (
            d * H * D + d * (z["C"] + z["Dr"])
            + z["C"] * H * (z["Dn"] + z["Dv"]) + H * z["Dv"] * d),
        "attention": L * S * S * H * (D + z["Dv"]),
        "dense_ffn": z["dense"] * S * 3 * 2 * d * z["Fd"],
        "router": n_moe * 2 * S * d * z["E"],
        "experts": n_moe * (S * z["k"] * z["held"] / z["E"])
        * 3 * 2 * d * z["F"],
        "shared_expert": n_moe * S * 3 * 2 * d * z["Fs"],
        "head": 2 * S * d * z["V"],
    }


def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes (twice the forward) of
    one sequence of the configuration's length under EVEN routing, no
    recompute (:func:`forward_flops_per_sample` says what is counted)."""
    return 3 * sum(forward_flops_per_sample(cfg).values())


def expert_product_flops(cfg, tokens_held):
    """FLOPs, forward and backward, of the three grouped products (gate,
    up, down) for ``tokens_held`` (token, choice, layer) triples that
    reached an expert held here."""
    z = dims(cfg)
    return 3 * tokens_held * 3 * 2 * z["d"] * z["F"]


def mla_attention_flops(cfg):
    """FLOPs of the causal attention cores a training step of one
    sequence needs, all layers, no recompute: per (query, key) pair of
    the lower triangle and head, QK^T over the keys' width and PV over
    the values' forward; dV and dP over the values' width and dQ, dK
    over the keys' backward.  The same need whatever a kernel pads."""
    z = dims(cfg)
    D, Dv = z["Dn"] + z["Dr"], z["Dv"]
    pairs = z["S"] * z["S"] / 2
    return z["L"] * 2 * pairs * z["H"] * (3 * D + 3 * Dv)


def mla_attention_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the
    attention cores, all layers: one read of q, k, v and one write of o
    (the model's dtype), and as much again for their gradients."""
    z = dims(cfg)
    D, Dv = z["Dn"] + z["Dr"], z["Dv"]
    return z["L"] * 2 * z["S"] * z["H"] * (2 * D + 2 * Dv) * bytes_per_value
