"""Plain reference of the Kimi-Linear decoder (Moonshot AI,
``model_type`` ``kimi_linear``, arXiv:2510.26692): Kimi Delta Attention
layers, a delta rule whose forget gate is one value a KEY CHANNEL, three
to every layer of multi-head latent attention WITHOUT position; a
leading dense SiLU-gated FFN (under a KDA mixer), then top-8 expert
sublayers behind independent sigmoid scores with a selection bias and an
ungated shared expert.  The layers as ISSUE 47 writes them from the
source's ``config.json``, the paper's equations and its public layer::

  every layer, input x (B, S, d):  x1 = x + Mixer(RMSNorm(x));  out = x1 + FFN(RMSNorm(x1))

  KDA mixer (h the normalised stream; H heads of D; per head unless said):
    q, k, v = h W_q^T, h W_k^T, h W_v^T                 # H D each, three weights
    q, k, v = silu(conv4(q)), silu(conv4(k)), silu(conv4(v))  # causal depthwise, zeros before 0
    q = l2norm(q) * D^-0.5 ;  k = l2norm(k)             # over a head's D channels
    a     = (h W_fa^T) W_fb^T                           # d -> D -> H D, no bias
    g_t   = -exp(A_log[head]) * softplus(a_t + dt_bias) # (D,) <= 0; A_log (H,), dt_bias (H D,)
    beta_t = sigmoid(h W_b^T)                           # one a head
    S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T   # S (Dk x Dv), S_0 = 0
    o_t   = S_t^T q_t
    o     = RMSNorm_D(o; gamma) * sigmoid((h W_ga^T) W_gb^T + b_g)
    y     = o W_o^T

  Latent attention without position: Kanana-2's (reference/kanana2.py)
    with the rotation left out: q (H x 192) = h W_q^T;  [c; kr] = h W_kva^T (512 + 64);
    [k0_h; v_h] = W_kvb,h RMSNorm(c);  k_h = [k0_h; kr] (kr shared by all heads, NOT turned);
    causal softmax at scale 192^-0.5, values 128 wide;  y = o W_o^T

  FFN: layer 1 a dense SiLU-gated FFN; every later layer
    s = sigmoid(h2 W_r^T) over all experts (float32);  C = top8(s + bias) (ties to the lower index)
    w = route_scale * s[C] / sum s[C]
    y = sum_{e in C, e held} w_e W_down,e(silu(W_gate,e h2) * (W_up,e h2)) + the shared expert (ungated)
  loss: mean next-token cross-entropy over the vocabulary slice, RMSNorm before the untied head

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel, no sort, NO CHUNK: the KDA state advances token
by token (``lax.scan`` over the sequence, in blocks under
``jax.checkpoint`` so that three float32 steps at 8192 tokens fit on one
chip; a block changes the order of no sum), which is what the program's
chunked algebra is held to; latent attention is a plain masked softmax
a block of query rows at a time; every held expert runs over every
token.  It imports nothing of ``mxnet_tpu`` and takes nothing the
program made: parameters come from :func:`init_leaf` and the selection
bias from :func:`init_aux`, by the names ``models/kimi_linear.py`` uses.

The chip's share, the departures and what is assumed: the
configuration's ``assumed`` and ``deployment``
(``benchmark/configs/kimi_linear_48b_train.json``).

``precision`` selects the arithmetic of the matmul operands of the
projections, attention, the feed-forwards and the head: ``"f32"`` is the
reference, ``"fp8"`` the control (``gpt2.mm_fp8``).  The router, the
gate ``g``, ``beta`` and the delta rule's state are float32 in the
control too: the architecture says so.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.kanana2 import (attention, experts, gated_ffn, init_aux,  # noqa: F401 (the interface)
                               expert_product_flops)
from reference.kanana2 import dims as _shared_dims
from reference.qwen3_next import CHUNK, SCAN_BLOCK, l2_norm, leaf_value  # noqa: F401 (the interface)
from reference.zaya import (data_shapes, device_batch, leaf_key,  # noqa: F401 (the interface)
                            make_batch, shift_right)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "kimi_linear.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'kimi_linear' (mxnet_tpu/models/"
                     "kimi_linear.py): the cell cannot run here")

RMS_EPS = 1e-5
ROW_BLOCK = 1024            # rows a block of the head


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def layer_kinds(cfg):
    """``["kda" | "full", ...]`` from the two published 1-based lists,
    cut to ``num_layers`` (a layer in both is full)."""
    full = set(int(i) for i in cfg["full_attn_layers"])
    kda = set(int(i) for i in cfg["kda_layers"])
    kinds = []
    for i in range(1, int(cfg["num_layers"]) + 1):
        if i not in full and i not in kda:
            raise ValueError("layer %d is in neither list" % i)
        kinds.append("full" if i in full else "kda")
    return kinds


def dims(cfg):
    """The sizes of ``kwargs``: the latent attention's and the expert
    sublayer's under Kanana-2's names, the KDA mixer's beside them."""
    z = _shared_dims(cfg)
    z.update(D=int(cfg["head_dim"]), K=int(cfg.get("conv_kernel", 4)),
             kinds=layer_kinds(cfg))
    return z


def layer_specs(cfg, i):
    z = dims(cfg)
    d, H, D, C = z["d"], z["H"], z["D"], z["C"]
    p = "layer%s_" % i
    out = [(p + "in_norm_gamma", (d,))]
    if z["kinds"][i] == "kda":
        out += [(p + "kda_%s_weight" % n, (H * D, d)) for n in "qkv"]
        out += [
            (p + "kda_conv_weight", (3 * H * D, z["K"])),
            (p + "kda_fa_weight", (D, d)), (p + "kda_fb_weight", (H * D, D)),
            (p + "kda_A_log", (H,)), (p + "kda_dt_bias", (H * D,)),
            (p + "kda_b_weight", (H, d)),
            (p + "kda_ga_weight", (D, d)), (p + "kda_gb_weight", (H * D, D)),
            (p + "kda_gb_bias", (H * D,)), (p + "kda_norm_gamma", (D,)),
            (p + "kda_o_weight", (d, H * D))]
    else:
        out += [
            (p + "attn_q_weight", (H * (z["Dn"] + z["Dr"]), d)),
            (p + "attn_kva_weight", (C + z["Dr"], d)),
            (p + "attn_kv_norm_gamma", (C,)),
            (p + "attn_kvb_weight", (H * (z["Dn"] + z["Dv"]), C)),
            (p + "attn_o_weight", (d, H * z["Dv"]))]
    out.append((p + "post_norm_gamma", (d,)))
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
    ``assumed``): the embedding normal(0, 1); every norm gain 1; the
    output gate's bias 0; ``A_log`` and ``dt_bias`` as Qwen3-Next's are
    (``reference/qwen3_next.py`` ``leaf_value``); every other weight
    normal(0, 0.02)."""
    if name.endswith("_gamma"):
        return "ones"
    if name.endswith("_gb_bias"):
        return "zeros"
    if name.endswith("_A_log"):
        return "a_log"
    if name.endswith("_dt_bias"):
        return "dt_bias"
    if name == "tok_embed_weight":
        return "embed"
    return "normal"


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def rms_norm(x, w):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(ms + RMS_EPS) * w


def kda_rule(q, k, v, g, beta):
    """The channel-gated delta rule, token by token.  ``q``, ``k`` (B, S,
    H, Dk) (q normalised and scaled, k normalised), ``v`` (B, S, H, Dv),
    ``g`` (B, S, H, Dk) and ``beta`` (B, S, H); the state (B, H, Dk, Dv)
    starts at 0 and its ROW d decays by ``exp(g_t[d])``.  Returns ``o``
    (B, S, H, Dv)."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    blk = min(SCAN_BLOCK, S)
    while S % blk:
        blk -= 1

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
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


def kimi_delta_attention(h, p, pre, z, precision):
    """The KDA mixer on the normalised stream (B, S, d)."""
    B, S, _ = h.shape
    H, D, K = z["H"], z["D"], z["K"]
    proj = lambda x, n: _mm("bsd,ed->bse", x, p[pre + "kda_%s_weight" % n],
                            precision)
    qkv = jnp.concatenate([proj(h, n) for n in "qkv"], -1)
    # causal depthwise convolution: tap K-1 is the position itself
    w = p[pre + "kda_conv_weight"]
    qkv = jax.nn.silu(sum(shift_right(qkv, K - 1 - j, 1) * w[:, j]
                          for j in range(K)))
    heads = lambda t: t.reshape(B, S, H, D)
    q, k, v = (heads(t) for t in jnp.split(qkv, 3, -1))
    a = heads(proj(proj(h, "fa"), "fb"))
    g = -jnp.exp(p[pre + "kda_A_log"])[:, None] * jax.nn.softplus(
        a + p[pre + "kda_dt_bias"].reshape(H, D))
    beta = jax.nn.sigmoid(proj(h, "b"))
    o = kda_rule(l2_norm(q) * D ** -0.5, l2_norm(k), v, g, beta)
    gate = heads(proj(proj(h, "ga"), "gb") + p[pre + "kda_gb_bias"])
    o = rms_norm(o, p[pre + "kda_norm_gamma"]) * jax.nn.sigmoid(gate)
    return _mm("bse,de->bsd", o.reshape(B, S, H * D),
               p[pre + "kda_o_weight"], precision)


def latent_attention(h, p, pre, z, precision):
    """The MLA sublayer without position on the normalised stream."""
    B, S, _ = h.shape
    H, Dn, Dr, Dv, C = z["H"], z["Dn"], z["Dr"], z["Dv"], z["C"]
    q = _mm("bsd,ed->bse", h, p[pre + "attn_q_weight"], precision) \
        .reshape(B, S, H, Dn + Dr)
    ckr = _mm("bsd,ed->bse", h, p[pre + "attn_kva_weight"], precision)
    c, k_shared = ckr[..., :C], ckr[..., C:]
    kv = _mm("bsc,ec->bse", rms_norm(c, p[pre + "attn_kv_norm_gamma"]),
             p[pre + "attn_kvb_weight"], precision).reshape(B, S, H, Dn + Dv)
    # ONE 64-wide key part a token, shared by all heads, not turned
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(k_shared[:, :, None, :],
                                        (B, S, H, Dr))], -1)
    o = attention(q, k, kv[..., Dn:], precision)
    return _mm("bse,de->bsd", o.reshape(B, S, H * Dv),
               p[pre + "attn_o_weight"], precision)


def block(x, p, aux, i, z, precision="f32"):
    """Layer ``i`` on (B, S, d): ``(x, chosen experts (B*S, k))``, the
    second None for a dense layer."""
    B, S, d = x.shape
    pre = "layer%d_" % i
    h = rms_norm(x, p[pre + "in_norm_gamma"])
    mixer = kimi_delta_attention if z["kinds"][i] == "kda" \
        else latent_attention
    x = x + mixer(h, p, pre, z, precision)
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
    gradient and is handed back as it came."""
    x, _ = forward(params, aux, tokens, cfg, precision)
    n = tokens.size
    total = head_loss(x.reshape(n, -1), labels.reshape(n), params, precision)
    return total / n, aux


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def _layers(z, kind):
    return sum(1 for k in z["kinds"] if k == kind)


def kda_scan_forward_flops(cfg):
    """Multiply-adds x 2 of the CHUNKED channel-gated delta rule's
    matrix products, forward, for one sequence over all KDA layers
    (chunk C = 64, as the program), counted as
    ``reference/qwen3_next.py`` ``gdn_scan_forward_flops`` counts the
    scalar gate's with one value head a key head: a chunk of a head has
    k k^T and q k^T (C x C x Dk each), the solved triangle times the
    decayed keys and times the values, ``W S``, ``q S``, ``k^T v_new``
    and the scores times the new values.  What the channel gate adds is
    no product: the decay inside the contraction turns the 16-row
    diagonal blocks of the two C x C x Dk squares into elementwise work
    of the same multiply-adds, counted here as the squares they are part
    of; the exponentials (C x Dk a reference row, 16 x C x Dk for the
    diagonal blocks), the triangle's solve and the running sums are not
    counted."""
    z = dims(cfg)
    C, Dk, Dv = CHUNK, z["D"], z["D"]
    head = 2 * (2 * C * C * Dk) + 2 * C * C * Dk + 2 * C * C * Dv \
        + 3 * (2 * C * Dk * Dv) + 2 * C * C * Dv
    return _layers(z, "kda") * (z["S"] / C) * z["H"] * head


def kda_scan_flops(cfg):
    """FLOPs of the chunked scan a training step of one sequence needs:
    forward and backward (twice the forward), no recompute."""
    return 3 * kda_scan_forward_flops(cfg)


def kda_scan_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the scan:
    one read of q, k, v (the model's dtype), of g (float32, as large as
    k) and beta (float32) and one write of o, and as much again for
    their gradients."""
    z = dims(cfg)
    values = z["S"] * z["H"] * (4 * z["D"] * bytes_per_value
                                + z["D"] * 4 + 4)
    return _layers(z, "kda") * 2 * values


def mla_attention_flops(cfg):
    """FLOPs of the causal attention cores a training step of one
    sequence needs over the latent-attention layers, no recompute
    (``reference/kanana2.py`` ``mla_attention_flops`` says what a pair
    costs)."""
    z = dims(cfg)
    D, Dv = z["Dn"] + z["Dr"], z["Dv"]
    pairs = z["S"] * z["S"] / 2
    return _layers(z, "full") * 2 * pairs * z["H"] * (3 * D + 3 * Dv)


def mla_attention_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the
    attention cores of the latent-attention layers: one read of q, k, v
    and one write of o, and as much again for their gradients."""
    z = dims(cfg)
    D, Dv = z["Dn"] + z["Dr"], z["Dv"]
    return _layers(z, "full") * 2 * z["S"] * z["H"] * (2 * D + 2 * Dv) \
        * bytes_per_value


def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``top_k * held / num_experts`` of a token's
    choices reach an expert held here.  Causal attention is counted at
    half the square: QK^T over 192 channels, PV over 128.  Lookups,
    norms, the convolution's 4 taps and other elementwise work are not
    counted."""
    z = dims(cfg)
    S, d, H, L, D = z["S"], z["d"], z["H"], z["L"], z["D"]
    Dq = z["Dn"] + z["Dr"]
    n_kda, n_full, n_moe = _layers(z, "kda"), _layers(z, "full"), \
        L - z["dense"]
    return {
        "kda_projections": n_kda * 2 * S * (
            4 * d * H * D + 2 * (d * D + D * H * D) + d * H),
        "kda_scan": kda_scan_forward_flops(cfg),
        "mla_projections": n_full * 2 * S * (
            d * H * Dq + d * (z["C"] + z["Dr"])
            + z["C"] * H * (z["Dn"] + z["Dv"]) + H * z["Dv"] * d),
        "attention": n_full * S * S * H * (Dq + z["Dv"]),
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
