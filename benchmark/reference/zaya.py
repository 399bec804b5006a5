"""Plain reference of the ZAYA1 decoder (Zyphra): compressed
convolutional attention (CCA, arXiv:2510.04476) and a top-1 expert
sublayer behind the ZAYA router (ZAYA1 technical report,
arXiv:2511.17127), as ISSUE 26 section 1 writes the layer equations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel, no sort, no grouped product: every held expert
runs over every token and a mask keeps the tokens routed to it.
Attention and the head are computed in blocks of rows under
``jax.checkpoint`` so that three float32 steps at 8192 tokens fit on one
chip; the blocks change the order of no sum.  It imports nothing of
``mxnet_tpu`` and takes nothing the program made: parameters come from
:func:`init_leaf`, by the names the repo's ``models/zaya.py`` uses.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts; ``experts_held = [first, count]`` says which
of them live here; a token whose expert is elsewhere gets ``y = 0`` from
the expert sublayer, here as in the program.  The vocabulary is the
slice ``num_classes``: ids, logits and the loss are over the slice.

Departures from the two papers, the program's and followed here so that
the comparison is of precision and not of architecture (the
configuration's ``assumed`` says each as a sentence): the key
temperature is one learned scalar a key/value head that multiplies the
normalised key; each sublayer's output has one learned per-channel
scale; the convolutions have no bias; the router's depth averaging is
``r_l = h Wr + gamma_l r_(l-1)`` with one learned scalar a layer; the
report's balancing bias is 0 and its update rule is absent; the sibling
configs' skip route (``zaya_use_mod``) is absent.  No other departure
is known to the author.

``precision`` selects the arithmetic of every matmul operand outside
the router: ``"f32"`` is the reference; ``"fp8"`` computes them the way
fp8 training does (gpt2.py's ``mm_fp8``): the control, the nearest
precision below the configuration's bfloat16.  The router is float32
whatever the model's precision (the architecture says so), in the
control too.
"""
import os
import zlib

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)

# The harness runs this reference BEFORE it builds the program's module
# (two minutes when it compiles), so a checkout whose program lacks the
# family would fail only then.  Looked up as a file beside the benchmark:
# nothing of the program is imported, here or anywhere in this module.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "zaya.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'zaya' (mxnet_tpu/models/zaya.py): the cell "
                     "cannot run here")

RMS_EPS = 1e-5
INIT_STD = 0.02
ROUTER_CARRY_INIT = 0.5     # gamma_l: nonzero, so a check exercises it
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
        "d": int(cfg["d_model"]), "Hq": int(cfg["q_heads"]),
        "Hk": int(cfg["kv_heads"]), "D": int(cfg["head_dim"]),
        "F": int(cfg["expert_dim"]), "E": E,
        "first": int(held[0]), "held": int(held[1]),
        "R": int(cfg["router_hidden"]),
        "K0": int(cfg.get("conv_k0", 2)), "K1": int(cfg.get("conv_k1", 2)),
        "rot": int(round(float(cfg.get("rotary_frac", 0.5))
                         * int(cfg["head_dim"]))),
        "theta": float(cfg.get("rope_theta", 5e6)),
        "S": int(cfg["seq_len"]),
    }


def layer_specs(cfg, i):
    """One layer's parameters.  The first layer's router receives no
    state (r_(-1) = 0), so it has no carry to learn."""
    z = dims(cfg)
    d, D, F, R = z["d"], z["D"], z["F"], z["R"]
    H = z["Hq"] + z["Hk"]
    p = "layer%s_" % i
    return [
        (p + "attn_norm_gamma", (d,)),
        (p + "attn_q_weight", (z["Hq"] * D, d)),
        (p + "attn_k_weight", (z["Hk"] * D, d)),
        (p + "attn_v_weight", (2 * D, d)),
        (p + "attn_conv0_weight", (H * D, z["K0"])),
        (p + "attn_conv1_weight", (H, D, D, z["K1"])),
        (p + "attn_temp", (z["Hk"],)),
        (p + "attn_o_weight", (d, z["Hq"] * D)),
        (p + "attn_scale", (d,)),
        (p + "moe_norm_gamma", (d,)),
        (p + "moe_router_in_weight", (R, d)),
    ] + ([] if i == 0 else [(p + "moe_router_carry", (1,))]) + [
        (p + "moe_router_norm_gamma", (R,)),
        (p + "moe_router_fc1_weight", (R, R)),
        (p + "moe_router_fc2_weight", (R, R)),
        (p + "moe_router_out_weight", (z["E"], R)),
        (p + "moe_gate_weight", (z["held"], F, d)),
        (p + "moe_up_weight", (z["held"], F, d)),
        (p + "moe_down_weight", (z["held"], d, F)),
        (p + "moe_scale", (d,)),
    ]


def param_specs(cfg):
    """[(name, shape)] of every parameter, in checkpoint order."""
    z = dims(cfg)
    out = [("tok_embed_weight", (z["V"], z["d"]))]
    for i in range(z["L"]):
        out += layer_specs(cfg, i)
    return out + [("final_norm_gamma", (z["d"],))]


def leaf_kind(name):
    """How a parameter is initialised, by its name: gains, scales and
    temperatures 1; the router's carry 0.5; a convolution's taps normal
    with the deviation 1/sqrt(fan-in) (2 taps; 2 x head_dim), so that
    both convolutions weigh as much as the query-key mean they are added
    to; every other weight GPT-2's normal(0, 0.02)."""
    if name.endswith(("_gamma", "_scale", "_temp")):
        return "ones"
    if name.endswith("_carry"):
        return "carry"
    if name.endswith(("_conv0_weight", "_conv1_weight")):
        return "fan_in"
    return "normal"


def leaf_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def leaf_value(k, kind, shape):
    """A parameter from ITS key, float32 and exact in bfloat16 (the
    caller casts to the type its side holds)."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "carry":
        return jnp.full(shape, ROUTER_CARRY_INIT, jnp.float32)
    std = INIT_STD
    if kind == "fan_in":
        # (channels, taps) or (heads, out, in, taps)
        fan = shape[-1] * (shape[-2] if len(shape) == 4 else 1)
        std = float(fan) ** -0.5
    w = jax.random.normal(k, shape, jnp.float32) * std
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def rms_norm(x, gain):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(ms + RMS_EPS) * gain


def shift_right(x, n, axis):
    """``x`` moved ``n`` positions later along ``axis``, zeros first
    (position t reads t - n; positions before 0 are 0)."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (n, 0)
    return lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[axis], axis=axis)


def rotary(x, rot, theta):
    """Rotary position on the first ``rot`` of the last axis of
    (B, S, H, D), the default pairing of halves (i with i + rot/2)."""
    S = x.shape[1]
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(q, k, v, group, precision):
    """Causal softmax attention of (B, S, Hq, D) queries on (B, S, Hk,
    D) keys and values, ``group`` query heads to a key/value head,
    scale 1/sqrt(D); a block of queries at a time."""
    B, S, Hq, D = q.shape
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    blk = min(Q_BLOCK, S)
    while S % blk:
        blk -= 1

    @jax.checkpoint
    def rows(qb, start):
        s = _mm("bqhe,bkhe->bhqk", qb, k, precision) / (D ** 0.5)
        mask = (start + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        a = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _mm("bhqk,bkhe->bqhe", a, v, precision)

    qb = q.reshape(B, S // blk, blk, Hq, D).transpose(1, 0, 2, 3, 4)
    out = lax.map(lambda a: rows(*a),
                  (qb, jnp.arange(S // blk) * blk))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, Hq, D)


def cca(h, p, pre, z, precision):
    """The CCA sublayer on the normalised stream (B, S, d)."""
    B, S, _ = h.shape
    Hq, Hk, D = z["Hq"], z["Hk"], z["D"]
    G = Hq // Hk
    q0 = _mm("bsd,ed->bse", h, p[pre + "attn_q_weight"], precision)
    k0 = _mm("bsd,ed->bse", h, p[pre + "attn_k_weight"], precision)
    # both convolutions are causal: tap K-1 is the position itself
    zc = jnp.concatenate([q0, k0], -1)                      # (B, S, C)
    w0 = p[pre + "attn_conv0_weight"]                       # (C, K0)
    z1 = sum(shift_right(zc, z["K0"] - 1 - j, 1) * w0[:, j]
             for j in range(z["K0"]))
    w1 = p[pre + "attn_conv1_weight"]                       # (H, o, i, K1)
    z1h = z1.reshape(B, S, Hq + Hk, D)
    z2 = sum(_mm("bshi,hoi->bsho",
                 shift_right(z1h, z["K1"] - 1 - j, 1), w1[..., j], precision)
             for j in range(z["K1"]))
    # the query-key mean, of the values before the convolutions
    q0h = q0.reshape(B, S, Hk, G, D)
    k0h = k0.reshape(B, S, Hk, 1, D)
    mq = 0.5 * (q0h + k0h)
    mk = jnp.mean(mq, axis=3)
    q = z2[:, :, :Hq] + mq.reshape(B, S, Hq, D)
    k = z2[:, :, Hq:] + mk
    # value: head 0 from this token, head 1 from the one before it
    v2 = _mm("bsd,ed->bse", h, p[pre + "attn_v_weight"], precision)
    v = jnp.stack([v2[..., :D], shift_right(v2[..., D:], 1, 1)], axis=2)
    if Hk != 2:
        raise ValueError("the value shift is written for 2 key/value heads")
    # unit length times sqrt(D); a learned temperature on each key head
    q = q * lax.rsqrt(jnp.sum(jnp.square(q), -1, keepdims=True)) * D ** 0.5
    k = k * lax.rsqrt(jnp.sum(jnp.square(k), -1, keepdims=True)) * D ** 0.5 \
        * p[pre + "attn_temp"][:, None]
    q, k = rotary(q, z["rot"], z["theta"]), rotary(k, z["rot"], z["theta"])
    o = attention(q, k, v, G, precision).reshape(B, S, Hq * D)
    return _mm("bse,de->bsd", o, p[pre + "attn_o_weight"], precision)


def router(h, r_prev, p, pre):
    """(router state, probabilities over ALL experts), float32 always."""
    r = _einsum("nd,rd->nr", h, p[pre + "moe_router_in_weight"])
    if pre + "moe_router_carry" in p:       # every layer but the first
        r = r + p[pre + "moe_router_carry"] * r_prev
    u = rms_norm(r, p[pre + "moe_router_norm_gamma"])
    u = jax.nn.gelu(_einsum("nr,or->no", u, p[pre + "moe_router_fc1_weight"]),
                    approximate=False)
    u = jax.nn.gelu(_einsum("nr,or->no", u, p[pre + "moe_router_fc2_weight"]),
                    approximate=False)
    s = _einsum("nr,er->ne", u, p[pre + "moe_router_out_weight"])
    return r, jax.nn.softmax(s, axis=-1)


def experts(h, r_prev, p, pre, z, precision):
    """The expert sublayer on normalised tokens (N, d): ``(y, router
    state, chosen expert (N,))``.  Every held expert runs over every
    token; the mask keeps its own."""
    r, prob = router(h, r_prev, p, pre)
    e = jnp.argmax(prob, axis=-1)               # ties: the lower index
    pe = jnp.take_along_axis(prob, e[:, None], axis=-1)[:, 0]

    def one(y, w):
        idx, wg, wu, wd = w
        g = _mm("nd,fd->nf", h, wg, precision)
        u = _mm("nd,fd->nf", h, wu, precision)
        out = _mm("nf,df->nd", jax.nn.silu(g) * u, wd, precision)
        return y + jnp.where((e == idx)[:, None], pe[:, None] * out, 0.0), None

    ids = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                    (ids, p[pre + "moe_gate_weight"],
                     p[pre + "moe_up_weight"], p[pre + "moe_down_weight"]))
    return y, r, e


def block(x, r, p, pre, z, precision="f32"):
    """One layer on (B, S, d) with the router state (B*S, R):
    ``(x, r, chosen experts (B*S,))``."""
    B, S, d = x.shape
    h = rms_norm(x, p[pre + "attn_norm_gamma"])
    x = x + cca(h, p, pre, z, precision) * p[pre + "attn_scale"]
    h = rms_norm(x, p[pre + "moe_norm_gamma"]).reshape(B * S, d)
    y, r, e = experts(h, r, p, pre, z, precision)
    return x + y.reshape(B, S, d) * p[pre + "moe_scale"], r, e


def head_loss(x, labels, p, precision):
    """Summed next-token cross-entropy of (N, d) rows against the tied
    embedding slice, a block of rows at a time."""
    N = x.shape[0]
    blk = min(Q_BLOCK, N)
    while N % blk:
        blk -= 1
    E = p["tok_embed_weight"]

    @jax.checkpoint
    def rows(xb, lb):
        logits = _mm("nd,vd->nv", rms_norm(xb, p["final_norm_gamma"]), E,
                     precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    parts = lax.map(lambda a: rows(*a), (x.reshape(N // blk, blk, -1),
                                         labels.reshape(N // blk, blk)))
    return jnp.sum(parts)


def forward(params, tokens, cfg, precision="f32"):
    """The residual stream after the last layer (B, S, d) and the
    expert every layer chose for every token (L, B*S)."""
    z = dims(cfg)
    B, S = tokens.shape
    x = params["tok_embed_weight"][tokens]
    r = jnp.zeros((B * S, z["R"]), jnp.float32)
    chosen = []
    for i in range(z["L"]):
        x, r, e = jax.checkpoint(
            lambda x, r, p, pre="layer%d_" % i: block(
                x, r, p, pre, z, precision))(x, r, params)
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
# the training cells' inputs
# ----------------------------------------------------------------------
def data_shapes(cfg, batch):
    S = int(cfg["seq_len"])
    return (batch, S), (batch * S,)


def make_batch(rng, cfg, batch):
    """Uniform token ids over the vocabulary slice and their next-token
    labels, as the float32 arrays an MXNet iterator hands over."""
    import numpy as np
    S, V = int(cfg["seq_len"]), int(cfg["num_classes"])
    tok = rng.integers(0, V, (batch, S))
    lab = np.roll(tok, -1, axis=1).reshape(batch * S)
    return tok.astype(np.float32), lab.astype(np.float32)


def device_batch(data, labels):
    tok = jnp.asarray(data).astype(jnp.int32)
    return tok, jnp.asarray(labels).astype(jnp.int32).reshape(tok.shape)


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``held/num_experts`` of the tokens reach an
    expert held here.  Causal attention is counted at half the square
    (QK^T and PV are 2*S*S*Hq*D each over the full square).  Embedding
    lookups, norms, rotary and other elementwise work are not counted."""
    z = dims(cfg)
    S, d, D = z["S"], z["d"], z["D"]
    q, kv = z["Hq"] * D, z["Hk"] * D
    per_layer = {
        "cca_projections": 2 * S * d * (q + kv + 2 * D) + 2 * S * q * d,
        "cca_convolutions": 2 * S * (q + kv) * z["K0"]
        + 2 * S * (z["Hq"] + z["Hk"]) * D * D * z["K1"],
        "attention": 2 * S * S * q,
        "router": 2 * S * (d * z["R"] + 2 * z["R"] * z["R"]
                           + z["R"] * z["E"]),
        "experts": (S * z["held"] / z["E"]) * 3 * 2 * d * z["F"],
    }
    out = {k: z["L"] * v for k, v in per_layer.items()}
    out["head"] = 2 * S * d * z["V"]
    return out


def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes (twice the forward) of
    one sequence of the configuration's length under EVEN routing, no
    recompute (:func:`forward_flops_per_sample` says what is counted)."""
    return 3 * sum(forward_flops_per_sample(cfg).values())


def expert_product_flops(cfg, tokens_held):
    """FLOPs, forward and backward, of the three grouped products (gate,
    up, down) for ``tokens_held`` (token, layer) pairs that reached an
    expert held here."""
    z = dims(cfg)
    return 3 * tokens_held * 3 * 2 * z["d"] * z["F"]
