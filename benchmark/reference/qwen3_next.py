"""Plain reference of the Qwen3-Next decoder (Qwen): three Gated
DeltaNet linear-attention layers (arXiv:2412.06464) to every gated
softmax-attention layer, every layer followed by a top-k expert sublayer
with one shared expert, as ISSUE 30 section 1 writes the layer equations
from the public ``modeling_qwen3_next.py``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel, no sort, no chunk: the delta rule runs token by
token (``lax.scan`` over the sequence, in blocks under ``jax.checkpoint``
so that three float32 steps at 8192 tokens fit on one chip; a block
changes the order of no sum), every held expert runs over every token
and a mask keeps the (token, choice) pairs routed to it.  It imports
nothing of ``mxnet_tpu`` and takes nothing the program made: parameters
come from :func:`init_leaf`, by the names ``models/qwen3_next.py`` uses.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts and keeps the ``top_k`` best with weights
normalised over all ``top_k``; ``experts_held = [first, count]`` says
which experts live here; a (token, choice) whose expert is elsewhere
adds 0, here as in the program.  The shared expert is whole on every
chip.  The vocabulary is the slice ``num_classes``.

Departures from the source (the configuration's ``assumed`` says each
as a sentence): no multi-token-prediction module, no auxiliary balancing
loss, ``intermediate_size`` unused (every layer is sparse), and q, k, v,
z are contiguous blocks of ``gdn_qkvz_weight``'s rows where the source
interleaves them by key head (a permutation of a random matrix).

``precision`` selects the arithmetic of the matmul operands of the
projections, the expert FFNs, attention and the head: ``"f32"`` is the
reference, ``"fp8"`` the control (``gpt2.mm_fp8``).  The router, the
decay ``g`` and the delta rule's state are float32 in the control too:
the architecture says so.
"""
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.zaya import (attention, data_shapes, device_batch,  # noqa: F401 (the interface)
                            leaf_key, make_batch, rotary, shift_right)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "qwen3_next.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'qwen3_next' (mxnet_tpu/models/qwen3_next.py): "
                     "the cell cannot run here")

RMS_EPS = 1e-6
L2_EPS = 1e-6
INIT_STD = 0.02
EMBED_STD = 1.0
SCAN_BLOCK = 64             # tokens a checkpointed block of the delta rule
ROW_BLOCK = 1024            # rows a block of the head
CHUNK = 64                  # the PROGRAM's chunk, for gdn_scan_flops only


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def layer_kinds(cfg):
    """``["linear" | "full", ...]``, one a layer: layer i is full when
    (i + 1) % full_attention_interval == 0."""
    n = int(cfg.get("full_attention_interval", 4))
    return ["full" if (i + 1) % n == 0 else "linear"
            for i in range(int(cfg["num_layers"]))]


def dims(cfg):
    """The sizes of ``kwargs`` as a dict of ints (and two floats)."""
    held = cfg.get("experts_held")
    E = int(cfg["num_experts"])
    if held is None:
        held = (0, E)
    elif isinstance(held, int):
        held = (0, held)
    D = int(cfg["head_dim"])
    return {
        "V": int(cfg["num_classes"]), "L": int(cfg["num_layers"]),
        "d": int(cfg["d_model"]), "Hq": int(cfg["q_heads"]),
        "Hk": int(cfg["kv_heads"]), "D": D,
        "rot": int(round(float(cfg.get("rotary_frac", 0.25)) * D)),
        "theta": float(cfg.get("rope_theta", 1e7)),
        "Gk": int(cfg["gdn_k_heads"]), "Gv": int(cfg["gdn_v_heads"]),
        "Dk": int(cfg["gdn_k_dim"]), "Dv": int(cfg["gdn_v_dim"]),
        "K": int(cfg.get("conv_kernel", 4)),
        "F": int(cfg["expert_dim"]), "Fs": int(cfg["shared_dim"]), "E": E,
        "k": int(cfg["top_k"]),
        "first": int(held[0]), "held": int(held[1]),
        "S": int(cfg["seq_len"]), "kinds": layer_kinds(cfg),
    }


def layer_specs(cfg, i):
    z = dims(cfg)
    d, F, Fs = z["d"], z["F"], z["Fs"]
    p = "layer%s_" % i
    if z["kinds"][i] == "linear":
        kd, vd = z["Gk"] * z["Dk"], z["Gv"] * z["Dv"]
        mixer = [
            (p + "gdn_qkvz_weight", (2 * kd + 2 * vd, d)),
            (p + "gdn_ba_weight", (2 * z["Gv"], d)),
            (p + "gdn_conv_weight", (2 * kd + vd, z["K"])),
            (p + "gdn_A_log", (z["Gv"],)),
            (p + "gdn_dt_bias", (z["Gv"],)),
            (p + "gdn_norm_gamma", (z["Dv"],)),
            (p + "gdn_out_weight", (d, vd)),
        ]
    else:
        q, kv = z["Hq"] * z["D"], z["Hk"] * z["D"]
        mixer = [
            (p + "attn_q_weight", (2 * q, d)),
            (p + "attn_k_weight", (kv, d)),
            (p + "attn_v_weight", (kv, d)),
            (p + "attn_q_norm_gamma", (z["D"],)),
            (p + "attn_k_norm_gamma", (z["D"],)),
            (p + "attn_o_weight", (d, q)),
        ]
    return [(p + "in_norm_gamma", (d,))] + mixer + [
        (p + "post_norm_gamma", (d,)),
        (p + "moe_router_weight", (z["E"], d)),
        (p + "moe_gate_weight", (z["held"], F, d)),
        (p + "moe_up_weight", (z["held"], F, d)),
        (p + "moe_down_weight", (z["held"], d, F)),
        (p + "moe_shared_gate_weight", (Fs, d)),
        (p + "moe_shared_up_weight", (Fs, d)),
        (p + "moe_shared_down_weight", (d, Fs)),
        (p + "moe_shared_sg_weight", (1, d)),
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
    zero-centred norm gains 0 and the gated norm's gain 1 (both the
    identity); ``A_log`` and ``dt_bias`` as the Gated DeltaNet reference
    initialises them; every other weight normal(0, 0.02)."""
    if name.endswith("gdn_norm_gamma"):
        return "ones"
    if name.endswith("_gamma"):
        return "zeros"
    if name.endswith("_A_log"):
        return "a_log"
    if name.endswith("_dt_bias"):
        return "dt_bias"
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
    if kind == "a_log":         # A uniform in (0, 16)
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-3, 16.0))
    if kind == "dt_bias":       # dt log-uniform in [0.001, 0.1]
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)
    std = EMBED_STD if kind == "embed" else INIT_STD
    w = jax.random.normal(k, shape, jnp.float32) * std
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def rms_norm(x, w):
    """``Qwen3NextRMSNorm``: the gain is ``1 + w``."""
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(ms + RMS_EPS) * (1.0 + w)


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token.  ``q``, ``k`` (B, S, H, Dk)
    (q normalised and scaled, k normalised), ``v`` (B, S, H, Dv), ``g``
    and ``beta`` (B, S, H); the state (B, H, Dk, Dv) starts at 0.
    Returns ``o`` (B, S, H, Dv)."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    blk = min(SCAN_BLOCK, S)
    while S % blk:
        blk -= 1

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - _einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, _einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    seq = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (S // blk, blk) + t.shape[:1] + t.shape[2:])
    _, o = lax.scan(block, jnp.zeros((B, H, Dk, Dv), jnp.float32),
                    tuple(seq(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1)


def gated_delta_net(h, p, pre, z, precision):
    """The Gated DeltaNet mixer on the normalised stream (B, S, d)."""
    B, S, _ = h.shape
    Gk, Gv, Dk, Dv, K = z["Gk"], z["Gv"], z["Dk"], z["Dv"], z["K"]
    kd, vd = Gk * Dk, Gv * Dv
    qkvz = _mm("bsd,ed->bse", h, p[pre + "gdn_qkvz_weight"], precision)
    qkv, zg = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    ba = _mm("bsd,ed->bse", h, p[pre + "gdn_ba_weight"], precision)
    b, a = ba[..., :Gv], ba[..., Gv:]
    # causal depthwise convolution: tap K-1 is the position itself
    w = p[pre + "gdn_conv_weight"]
    qkv = jax.nn.silu(sum(shift_right(qkv, K - 1 - j, 1) * w[:, j]
                          for j in range(K)))
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p[pre + "gdn_A_log"]) * jax.nn.softplus(
        a + p[pre + "gdn_dt_bias"])
    rep = Gv // Gk                      # value heads a key head serves
    heads = lambda t, n, D: t.reshape(B, S, n, D)
    q = jnp.repeat(l2_norm(heads(qkv[..., :kd], Gk, Dk)), rep, axis=2) \
        * Dk ** -0.5
    k = jnp.repeat(l2_norm(heads(qkv[..., kd:2 * kd], Gk, Dk)), rep, axis=2)
    o = delta_rule(q, k, heads(qkv[..., 2 * kd:], Gv, Dv), g, beta)
    # RMSNormGated, a value head at a time
    ms = jnp.mean(jnp.square(o), -1, keepdims=True)
    o = p[pre + "gdn_norm_gamma"] * o * lax.rsqrt(ms + RMS_EPS) \
        * jax.nn.silu(heads(zg, Gv, Dv))
    return _mm("bse,de->bsd", o.reshape(B, S, vd), p[pre + "gdn_out_weight"],
               precision)


def gated_attention(h, p, pre, z, precision):
    """The gated softmax-attention mixer on the normalised stream."""
    B, S, _ = h.shape
    Hq, Hk, D = z["Hq"], z["Hk"], z["D"]
    qg = _mm("bsd,ed->bse", h, p[pre + "attn_q_weight"], precision) \
        .reshape(B, S, Hq, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = _mm("bsd,ed->bse", h, p[pre + "attn_k_weight"], precision) \
        .reshape(B, S, Hk, D)
    v = _mm("bsd,ed->bse", h, p[pre + "attn_v_weight"], precision) \
        .reshape(B, S, Hk, D)
    q = rotary(rms_norm(q, p[pre + "attn_q_norm_gamma"]), z["rot"], z["theta"])
    k = rotary(rms_norm(k, p[pre + "attn_k_norm_gamma"]), z["rot"], z["theta"])
    o = attention(q, k, v, Hq // Hk, precision) * jax.nn.sigmoid(gate)
    return _mm("bse,de->bsd", o.reshape(B, S, Hq * D),
               p[pre + "attn_o_weight"], precision)


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
    """The expert sublayer on normalised tokens (N, d): ``(routed part
    of the experts held here, shared expert's part, chosen experts
    (N, k))``; the sublayer's result is the sum of the two parts.  Every
    held expert runs over every token; the mask keeps its own pairs."""
    e, w = route(h, p, pre, z)

    def one(y, xs):
        idx, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(e == idx, w, 0.0), -1, keepdims=True)
        return y + mine * gated_ffn(h, wg, wu, wd, precision), None

    ids = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                    (ids, p[pre + "moe_gate_weight"],
                     p[pre + "moe_up_weight"], p[pre + "moe_down_weight"]))
    s = jax.nn.sigmoid(_mm("nd,od->no", h, p[pre + "moe_shared_sg_weight"],
                           precision)) * gated_ffn(
        h, p[pre + "moe_shared_gate_weight"], p[pre + "moe_shared_up_weight"],
        p[pre + "moe_shared_down_weight"], precision)
    return y, s, e


def block(x, p, i, z, precision="f32"):
    """Layer ``i`` on (B, S, d): ``(x, chosen experts (B*S, k))``."""
    B, S, d = x.shape
    pre = "layer%d_" % i
    h = rms_norm(x, p[pre + "in_norm_gamma"])
    mixer = gated_delta_net if z["kinds"][i] == "linear" else gated_attention
    x = x + mixer(h, p, pre, z, precision)
    h = rms_norm(x, p[pre + "post_norm_gamma"]).reshape(B * S, d)
    y, s, e = experts(h, p, pre, z, precision)
    return x + (y + s).reshape(B, S, d), e


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
    """The residual stream after the last layer (B, S, d) and the
    experts every layer chose for every token (L, B*S, k)."""
    z = dims(cfg)
    x = params["tok_embed_weight"][tokens]
    chosen = []
    for i in range(z["L"]):
        x, e = jax.checkpoint(
            lambda x, p, i=i: block(x, p, i, z, precision))(x, params)
        chosen.append(e)
    return x, jnp.stack(chosen)


def loss(params, aux, tokens, labels, cfg, precision="f32"):
    """Mean next-token cross-entropy over every position: what the
    repo's ``ce`` metric reads and what SoftmaxOutput with
    ``normalization='batch'`` differentiates."""
    x, _ = forward(params, tokens, cfg, precision)
    n = tokens.size
    total = head_loss(x.reshape(n, -1), labels.reshape(n), params, precision)
    return total / n, aux


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def _gdn_layers(z):
    return sum(1 for kind in z["kinds"] if kind == "linear")


def gdn_scan_forward_flops(cfg):
    """Multiply-adds x 2 of the CHUNKED gated delta rule's matrix
    products, forward, for one sequence over all Gated DeltaNet layers
    (chunk C = 64, as the program and the source's
    ``chunk_gated_delta_rule``).  A chunk of a key head: k k^T and q k^T
    (C x C x Dk each).  A chunk of a value head: the solved triangle
    times the decayed keys and times the values (C x C x Dk, C x C x
    Dv); against the state, ``W S`` and ``q S`` and ``k^T v_new`` (C x
    Dk x Dv each); inside the chunk the masked scores times the new
    values (C x C x Dv).  The triangle's solve (C^3 / 3 a chunk) and the
    elementwise work are not counted."""
    z = dims(cfg)
    C, Dk, Dv = CHUNK, z["Dk"], z["Dv"]
    chunks = z["S"] / C
    key_head = 2 * (2 * C * C * Dk)
    value_head = 2 * C * C * Dk + 2 * C * C * Dv \
        + 3 * (2 * C * Dk * Dv) + 2 * C * C * Dv
    return _gdn_layers(z) * chunks * (z["Gk"] * key_head
                                      + z["Gv"] * value_head)


def gdn_scan_flops(cfg):
    """FLOPs of the chunked scan a training step of one sequence needs:
    forward and backward (twice the forward), no recompute."""
    return 3 * gdn_scan_forward_flops(cfg)


def gdn_scan_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the scan:
    one read of q, k, v (the model's dtype), of g and beta (float32) and
    one write of o, and as much again for their gradients."""
    z = dims(cfg)
    S = z["S"]
    values = S * (2 * z["Gk"] * z["Dk"] + 2 * z["Gv"] * z["Dv"]) \
        * bytes_per_value + S * 2 * z["Gv"] * 4
    return _gdn_layers(z) * 2 * values


def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``top_k * held / num_experts`` of a token's
    choices reach an expert held here.  Causal attention is counted at
    half the square.  Lookups, norms, the convolution's 4 taps, rotary
    and other elementwise work are not counted."""
    z = dims(cfg)
    S, d = z["S"], z["d"]
    kd, vd = z["Gk"] * z["Dk"], z["Gv"] * z["Dv"]
    q, kv = z["Hq"] * z["D"], z["Hk"] * z["D"]
    n_lin = _gdn_layers(z)
    return {
        "gdn_projections": n_lin * 2 * S * d * (2 * kd + 3 * vd + 2 * z["Gv"]),
        "gdn_scan": gdn_scan_forward_flops(cfg),
        "attn_projections": (z["L"] - n_lin) * 2 * S * d * (3 * q + 2 * kv),
        "attention": (z["L"] - n_lin) * 2 * S * S * q,
        "router": z["L"] * 2 * S * d * z["E"],
        "experts": z["L"] * (S * z["k"] * z["held"] / z["E"])
        * 3 * 2 * d * z["F"],
        "shared_expert": z["L"] * S * (3 * 2 * d * z["Fs"] + 2 * d),
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
