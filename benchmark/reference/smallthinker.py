"""Plain reference of SmallThinker-21BA3B-Instruct's language model
(PowerInfer): window and full grouped-query attention layers in one
model, a router that reads the layer's input before attention, and
ReLU-gated experts, as the source's ``config.json`` gives the layer
(ISSUE 40, Tentpole).

``d = 2560``, 52 layers, ``Hq = 28``, ``Hk = 4``, ``D = 128``, ``E =
64`` experts of width ``F = 768``, top ``k = 6``, vocabulary 151 936
untied, ``rms_norm_eps`` 1e-6, no bias anywhere.  For layer ``l`` with
input ``x`` (B, S, d)::

    r      = x W_r^T                                  # (B,S,64) float32; W_r (64, d): the router reads the layer's INPUT
    h      = RMSNorm(x; g_in)
    q,k,v  = h W_q^T, h W_k^T, h W_v^T                # 28, 4, 4 heads of 128
    if rope_layout[l] == 1:  q, k = rope(q), rope(k)  # all 128 channels, halves paired, theta 1.5e6, positions 0..S-1
                                                      # rope_layout[l] == 0: no position at all
    allowed(t, s) = s <= t  and  (sliding_window_layout[l] == 0  or  t - s < 4096)
    a      = softmax_s( q_t . k_s / sqrt(128)  over allowed )  v        # head j reads key/value head j // 7
    x1     = x + a W_o^T
    h2     = RMSNorm(x1; g_post)
    C      = top6(r)  (ties to the lower index);  w = softmax over the 6 chosen logits r[C]
                                                      # = softmax over all 64, top 6, renormalised: moe_primary_router_apply_softmax, norm_topk_prob
    y      = sum_{e in C} w_e * W_down,e ( relu(W_gate,e h2) * (W_up,e h2) )
    out    = x1 + y

Both lists have period 4: layers 0, 4, 8, ... are full attention without
position; the three after each are window 4096 with rotary.  Then a
final RMSNorm and ``lm_head``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel: the mask is a plain ``where`` over the (S, S)
scores, a block of query rows against all S keys at a time under
``jax.checkpoint`` so that three float32 steps at 16 384 tokens fit on
one chip (a block changes the order of no sum); every held expert runs
over every token and a mask keeps the (token, choice) pairs routed to
it.  It imports nothing of ``mxnet_tpu`` and takes nothing the program
made: parameters come from :func:`init_leaf`, by the names
``models/smallthinker.py`` uses.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts and keeps the ``top_k`` best with weights
normalised over all ``top_k``; ``experts_held = [first, count]`` says
which experts live here; a (token, choice) whose expert is elsewhere
adds 0, here as in the program.  The vocabulary is the slice
``num_classes``.

Departures from the source (the configuration's ``assumed`` says each
as a sentence): the router reads the UN-normalised layer input; a window
counts the query's own position; no q/k norm and no attention bias; no
secondary experts and no balancing loss.

``precision`` selects the arithmetic of the matmul operands of the
attention's projections and products, the expert FFNs and the head:
``"f32"`` is the reference, ``"fp8"`` the control (``gpt2.mm_fp8``).
The router is float32 in the control too: the architecture says so.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _einsum, _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.keye_vl2 import (INIT_STD, EMBED_STD, head_loss,  # noqa: F401 (the interface)
                                leaf_value, rms_norm)
from reference.zaya import (data_shapes, device_batch, leaf_key,  # noqa: F401 (the interface)
                            make_batch, rotary)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "smallthinker.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'smallthinker' (mxnet_tpu/models/"
                     "smallthinker.py): the cell cannot run here")

Q_BLOCK = 256               # query rows a block of attention


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def dims(cfg):
    """The sizes of ``kwargs`` as a dict of ints (a float, two lists)."""
    held = cfg.get("experts_held")
    E, L = int(cfg["num_experts"]), int(cfg["num_layers"])
    if held is None:
        held = (0, E)
    elif isinstance(held, int):
        held = (0, held)
    return {
        "V": int(cfg["num_classes"]), "L": L, "d": int(cfg["d_model"]),
        "Hq": int(cfg["q_heads"]), "Hk": int(cfg["kv_heads"]),
        "D": int(cfg["head_dim"]),
        "theta": float(cfg.get("rope_theta", 1.5e6)),
        "W": int(cfg["window"]),
        "windowed": [int(v) for v in cfg["window_layout"]],
        "turned": [int(v) for v in cfg["rope_layout"]],
        "F": int(cfg["expert_dim"]), "E": E, "k": int(cfg["top_k"]),
        "first": int(held[0]), "held": int(held[1]),
        "S": int(cfg["seq_len"]),
    }


def layer_specs(cfg, i):
    z = dims(cfg)
    d, Hq, Hk, D = (z[n] for n in ("d", "Hq", "Hk", "D"))
    p = "layer%s_" % i
    return [
        (p + "in_norm_gamma", (d,)),
        (p + "attn_q_weight", (Hq * D, d)),
        (p + "attn_k_weight", (Hk * D, d)),
        (p + "attn_v_weight", (Hk * D, d)),
        (p + "attn_o_weight", (d, Hq * D)),
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


def leaf_kind(name):
    """How a parameter is initialised, by its name (the configuration's
    ``assumed.init``): the embedding normal(0, 1); the norm gains 1;
    every other weight normal(0, 0.02)."""
    if name.endswith("_gamma"):
        return "ones"
    if name == "tok_embed_weight":
        return "embed"
    return "normal"


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def allowed(t, s, window):
    """Whether query position ``t`` attends key position ``s``: every
    ``s <= t``, and with ``window`` (0: none) only ``t - s < window``."""
    ok = s <= t
    return ok & (t - s < window) if window else ok


def attention(q, k, v, window, z, precision, blk=None):
    """``o`` (B, S, Hq, D): every query's softmax over its allowed keys,
    a block of query rows against all S keys at a time."""
    B, S, Hq, D = q.shape
    Hk, R = z["Hk"], z["Hq"] // z["Hk"]
    blk = min(Q_BLOCK, S) if blk is None else blk
    while S % blk:
        blk -= 1

    @jax.checkpoint
    def rows(qb, start):
        on = allowed((start + jnp.arange(blk))[:, None],
                     jnp.arange(S)[None, :], window)
        s = _mm("bqgre,bkge->bgrqk", qb.reshape(B, blk, Hk, R, D), k,
                precision) * D ** -0.5
        a = jax.nn.softmax(jnp.where(on, s, -1e30), -1)
        return _mm("bgrqk,bkge->bqgre", a, v, precision) \
            .reshape(B, blk, Hq, D)

    cut = q.reshape(B, S // blk, blk, Hq, D).swapaxes(0, 1)
    o = lax.map(lambda a: rows(*a), (cut, jnp.arange(S // blk) * blk))
    return o.swapaxes(0, 1).reshape(B, S, Hq, D)


def attention_sublayer(h, p, pre, i, z, precision, blk=None):
    """Layer ``i``'s attention sublayer on the normalised stream
    (B, S, d): window or full, turned or not, as the two lists say."""
    B, S, _ = h.shape
    Hq, Hk, D = z["Hq"], z["Hk"], z["D"]
    heads = lambda name, n: _mm("bsd,ed->bse", h, p[pre + name],
                                precision).reshape(B, S, n, D)
    q, k, v = (heads("attn_q_weight", Hq), heads("attn_k_weight", Hk),
               heads("attn_v_weight", Hk))
    if z["turned"][i]:
        q, k = rotary(q, D, z["theta"]), rotary(k, D, z["theta"])
    o = attention(q, k, v, z["W"] if z["windowed"][i] else 0, z, precision,
                  blk)
    return _mm("bse,de->bsd", o.reshape(B, S, Hq * D),
               p[pre + "attn_o_weight"], precision)


def gated_ffn(h, wg, wu, wd, precision):
    g = _mm("nd,fd->nf", h, wg, precision)
    u = _mm("nd,fd->nf", h, wu, precision)
    return _mm("nf,df->nd", jax.nn.relu(g) * u, wd, precision)


def route(x, p, pre, z):
    """The ``top_k`` experts of every token (N, k) and their weights
    from the rows ``x`` the ROUTER reads: the softmax over the chosen
    logits (the softmax over all experts, its ``top_k`` best,
    renormalised); float32 always.  ``lax.top_k`` gives the lower index
    first among equals."""
    prob = jax.nn.softmax(_einsum("nd,ed->ne", x, p[pre + "moe_router_weight"]),
                          axis=-1)
    w, e = lax.top_k(prob, z["k"])
    return e, w / jnp.sum(w, -1, keepdims=True)


def experts(h, x_router, p, pre, z, precision):
    """The expert sublayer: the experts read the normalised tokens ``h``
    (N, d), the router reads ``x_router`` (N, d).  ``(the part of the
    experts held here, chosen experts (N, k))``.  Every held expert runs
    over every token; the mask keeps its own pairs."""
    e, w = route(x_router, p, pre, z)

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
    """Layer ``i`` on (B, S, d)."""
    B, S, d = x.shape
    pre = "layer%d_" % i
    x1 = x + attention_sublayer(rms_norm(x, p[pre + "in_norm_gamma"]), p,
                                pre, i, z, precision)
    h2 = rms_norm(x1, p[pre + "post_norm_gamma"]).reshape(B * S, d)
    y, _ = experts(h2, x.reshape(B * S, d), p, pre, z, precision)
    return x1 + y.reshape(B, S, d)


def forward(params, tokens, cfg, precision="f32"):
    """The residual stream after the last layer (B, S, d)."""
    z = dims(cfg)
    x = params["tok_embed_weight"][tokens]
    for i in range(z["L"]):
        x = jax.checkpoint(
            lambda x, p, i=i: block(x, p, i, z, precision))(x, params)
    return x


def loss(params, aux, tokens, labels, cfg, precision="f32"):
    """Mean next-token cross-entropy over the vocabulary slice."""
    x = forward(params, tokens, cfg, precision)
    n = tokens.size
    return head_loss(x.reshape(n, -1), labels.reshape(n), params,
                     precision) / n, aux


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def pairs(cfg):
    """``(causal, band)`` (query, key) pairs of one sequence and head:
    every s <= t, and min(t + 1, window) of them a query."""
    z = dims(cfg)
    S, W = z["S"], min(z["W"], z["S"])
    return S * (S + 1) // 2, W * (W + 1) // 2 + (S - W) * W


def layer_pairs(cfg):
    """The pairs of each layer, by its kind."""
    causal, band = pairs(cfg)
    return [band if w else causal for w in dims(cfg)["windowed"]]


def window_attention_flops(cfg):
    """FLOPs of the WINDOW layers' cores a training step of one sequence
    needs, the band's pairs only, no recompute: per pair and head QK^T
    and PV forward; dV, dP, dQ, dK backward."""
    z = dims(cfg)
    return sum(z["windowed"]) * 2 * pairs(cfg)[1] * z["Hq"] * 6 * z["D"]


def window_attention_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the window
    layers' cores: one read of q, k, v and one write of o (the model's
    dtype), and as much again for their gradients."""
    z = dims(cfg)
    return sum(z["windowed"]) * 2 * z["S"] * z["D"] \
        * (2 * z["Hq"] + 2 * z["Hk"]) * bytes_per_value


def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part.
    Routing is counted EVEN: ``top_k * held / num_experts`` of a token's
    choices reach an expert held here.  The cores are counted at the
    band's pairs in the window layers and at the triangle's in the full
    ones.  Lookups, norms, rotary and other elementwise work are not
    counted."""
    z = dims(cfg)
    S, d, L = z["S"], z["d"], z["L"]
    return {
        "projections": L * 2 * S * d * z["D"] * (2 * z["Hq"] + 2 * z["Hk"]),
        "attention": sum(layer_pairs(cfg)) * 2 * z["Hq"] * 2 * z["D"],
        "router": L * 2 * S * d * z["E"],
        "experts": L * (S * z["k"] * z["held"] / z["E"]) * 3 * 2 * d * z["F"],
        "head": 2 * S * d * z["V"],
    }


def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes of one sequence of the
    configuration's length under EVEN routing, no recompute: twice the
    forward going back."""
    return 3 * sum(forward_flops_per_sample(cfg).values())


def expert_product_flops(cfg, tokens_held):
    """FLOPs, forward and backward, of the three grouped products (gate,
    up, down) for ``tokens_held`` (token, choice, layer) triples that
    reached an expert held here."""
    z = dims(cfg)
    return 3 * tokens_held * 3 * 2 * z["d"] * z["F"]
