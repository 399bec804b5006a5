"""Plain reference of SDAR-30B-A3B-Chat's language model (JetLM;
``model_type`` ``sdar_moe``) and of the block-diffusion objective it is
trained by, as the source's ``config.json`` gives the layer (ISSUE 44,
Tentpole).

``d = 2048``, 48 identical layers, ``Hq = 32``, ``Hk = 4``, ``D = 128``,
``E = 128`` experts of width ``F = 768``, top ``k = 8``,
``norm_topk_prob``, ``rope_theta`` 1e6, ``rms_norm_eps`` 1e-6,
vocabulary 151 936 untied, no bias, no shared expert, no window.  Block
length ``Bk = 4`` and the mask probability are the configuration's
``assumed``.

A training pass of one sequence ``x0`` (L tokens) runs ``R = 2L`` rows:
rows ``0..L-1`` are ``x0`` (clean), rows ``L..2L-1`` are ``xt``, where
``xt_i = MASK`` if ``m_i`` else ``x0_i``.  ``pos(r) = r mod L``,
``blk(r) = pos(r) // Bk``, ``noised(r) = r >= L``::

    allowed(t, s) =  not noised(t) and not noised(s) and blk(s) <= blk(t)      # clean sees clean, block-causal (its own block both ways)
                  or     noised(t) and not noised(s) and blk(s) <  blk(t)      # noised sees the clean blocks strictly before its own
                  or     noised(t) and     noised(s) and blk(s) == blk(t)      # noised sees its own block, both ways
                                                                               # clean never sees noised
    for each layer, input x (B, R, d):
    h      = RMSNorm(x; g_in)
    q,k,v  = h W_q^T, h W_k^T, h W_v^T                 # 32, 4, 4 heads of 128
    q, k   = RMSNorm_128(q; g_q), RMSNorm_128(k; g_k)  # over each head's 128 channels, one gain vector for all heads
    q, k   = rope(q, pos), rope(k, pos)                # all 128 channels, halves paired, theta 1e6; row L+i turns as row i
    a      = softmax_s( q_t . k_s / sqrt(128)  over allowed(t, s) ) v      # head j reads key/value head j // 8
    x1     = x + a W_o^T
    h2     = RMSNorm(x1; g_post)
    p      = softmax(h2 W_r^T) over all 128 (float32);  C = top8(p) (ties to the lower index);  w = p[C] / sum p[C]
    y      = sum_{e in C} w_e * W_down,e ( silu(W_gate,e h2) * (W_up,e h2) )
    out    = x1 + y
    after the last layer, for i in 0..L-1:   z_i = lm_head( RMSNorm(out[L + i]; g_final) )        # the noised half only
    J      = (1 / L) * sum_i  m_i * (1 / p_blk(i)) * ( -log softmax(z_i)[x0_i] )                  # what is minimised
    ce     = (1 / L) * sum_i  m_i *                  ( -log softmax(z_i)[x0_i] )                  # what the `ce` metric reads

The noise is made here, from the run's seed, as a collator makes it
(:func:`make_batch`): for each block ``b`` draw ``u_b ~ U(0, 1)``,
``p_b = (1 - 1e-3) u_b + 1e-3``; mask each of its ``Bk`` tokens
independently with probability ``p_b``.  ``data`` is one float32 array
(B, 3, L): ``x0``, ``xt`` and the row weight ``m_i / p_blk(i)``;
``softmax_label`` is ``x0`` (B x L).  The prediction is at the token's
own row (no shift).  ``MASK`` is the vocabulary slice's last row; ids
and labels are drawn from the rows before it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision.  No kernel: the mask is a plain ``where`` over the (R, R)
scores, a block of query rows against all R keys at a time under
``jax.checkpoint`` so that three float32 steps at 16 384 rows fit on one
chip (a block changes the order of no sum); every held expert runs over
every row and a mask keeps the (row, choice) pairs routed to it.  It
imports nothing of ``mxnet_tpu`` and takes nothing the program made:
parameters come from :func:`init_leaf`, by the names
``models/sdar_moe.py`` uses.

The chip's share (``model-configs`` guide, section 4): the router scores
all ``num_experts`` experts and keeps the ``top_k`` best with weights
normalised over all ``top_k``; ``experts_held = [first, count]`` says
which experts live here; a (row, choice) whose expert is elsewhere adds
0, here as in the program.  The vocabulary is the slice ``num_classes``.

:func:`loss` returns the VALUE ``ce`` (what the repo's ``ce`` metric
reads) with the GRADIENT of ``J`` (the objective):
``stop_gradient(ce) + (J - stop_gradient(J))``.

``precision`` selects the arithmetic of the matmul operands of the
attention's projections and products, the expert FFNs and the head:
``"f32"`` is the reference, ``"fp8"`` the control (``gpt2.mm_fp8``).
The router is float32 in the control too: the architecture says so.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import _mm, seed_key  # noqa: F401 (seed_key: the interface)
from reference.keye_vl2 import (INIT_STD, EMBED_STD, experts,  # noqa: F401 (the interface)
                                leaf_kind, leaf_value, rms_norm)
from reference.zaya import leaf_key, rotary  # noqa: F401 (leaf_key: the interface)

# As reference/zaya.py: a checkout whose program lacks the family fails
# here, at once, and not after the reference has compiled and run.
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..",
        "mxnet_tpu", "models", "sdar_moe.py")):
    raise SystemExit("benchmark: this checkout's program has no model "
                     "family 'sdar_moe' (mxnet_tpu/models/sdar_moe.py): "
                     "the cell cannot run here")

Q_BLOCK = 256               # query rows a block of attention
ROW_BLOCK = 1024            # rows a block of the head
P_FLOOR = 1e-3              # the least mask probability of a block


# ----------------------------------------------------------------------
# sizes and parameters by name
# ----------------------------------------------------------------------
def dims(cfg):
    """The sizes of ``kwargs`` as a dict of ints (and a float)."""
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
        "theta": float(cfg.get("rope_theta", 1e6)),
        "Bk": int(cfg.get("block_length", 4)),
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
        (p + "attn_q_norm_gamma", (D,)),
        (p + "attn_k_norm_gamma", (D,)),
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


def init_leaf(key, name, shape):
    """The embedding normal(0, 1); the norm gains 1; every other weight
    normal(0, 0.02) (``keye_vl2.leaf_kind``, by the name)."""
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def allowed(t, s, S, Bk):
    """Whether row ``t`` of the ``2 S`` attends row ``s``: the three
    parts of the block-diffusion mask, written out."""
    noised_t, noised_s = t >= S, s >= S
    blk_t, blk_s = (t % S) // Bk, (s % S) // Bk
    return (~noised_t & ~noised_s & (blk_s <= blk_t)) \
        | (noised_t & ~noised_s & (blk_s < blk_t)) \
        | (noised_t & noised_s & (blk_s == blk_t))


def attention(q, k, v, z, precision, blk=None):
    """``o`` (B, R, Hq, D): every row's softmax over its allowed rows, a
    block of query rows against all R keys at a time."""
    B, R, Hq, D = q.shape
    Hk, G = z["Hk"], z["Hq"] // z["Hk"]
    blk = min(Q_BLOCK, R) if blk is None else blk
    while R % blk:
        blk -= 1

    @jax.checkpoint
    def rows(qb, start):
        on = allowed((start + jnp.arange(blk))[:, None],
                     jnp.arange(R)[None, :], R // 2, z["Bk"])
        s = _mm("bqgre,bkge->bgrqk", qb.reshape(B, blk, Hk, G, D), k,
                precision) * D ** -0.5
        a = jax.nn.softmax(jnp.where(on, s, -1e30), -1)
        return _mm("bgrqk,bkge->bqgre", a, v, precision) \
            .reshape(B, blk, Hq, D)

    cut = q.reshape(B, R // blk, blk, Hq, D).swapaxes(0, 1)
    o = lax.map(lambda a: rows(*a), (cut, jnp.arange(R // blk) * blk))
    return o.swapaxes(0, 1).reshape(B, R, Hq, D)


def turned(x, z):
    """Rotary position on all channels of (B, R, H, D): each half by its
    own positions ``0 .. S - 1`` (row ``S + i`` turns as row ``i``)."""
    B, R, H, D = x.shape
    halves = x.reshape(B * 2, R // 2, H, D)
    return rotary(halves, D, z["theta"]).reshape(x.shape)


def attention_sublayer(h, p, pre, z, precision, blk=None):
    """The attention sublayer on the normalised stream (B, R, d)."""
    B, R, _ = h.shape
    Hq, Hk, D = z["Hq"], z["Hk"], z["D"]
    heads = lambda name, n: _mm("bsd,ed->bse", h, p[pre + name],
                                precision).reshape(B, R, n, D)
    q, k, v = (heads("attn_q_weight", Hq), heads("attn_k_weight", Hk),
               heads("attn_v_weight", Hk))
    q = turned(rms_norm(q, p[pre + "attn_q_norm_gamma"]), z)
    k = turned(rms_norm(k, p[pre + "attn_k_norm_gamma"]), z)
    o = attention(q, k, v, z, precision, blk)
    return _mm("bse,de->bsd", o.reshape(B, R, Hq * D),
               p[pre + "attn_o_weight"], precision)


def block(x, p, i, z, precision="f32"):
    """Layer ``i`` on (B, R, d)."""
    B, R, d = x.shape
    pre = "layer%d_" % i
    x = x + attention_sublayer(rms_norm(x, p[pre + "in_norm_gamma"]), p,
                               pre, z, precision)
    h = rms_norm(x, p[pre + "post_norm_gamma"]).reshape(B * R, d)
    y, _ = experts(h, p, pre, z, precision)
    return x + y.reshape(B, R, d)


def trunk(params, x0, xt, cfg, precision="f32"):
    """The residual stream of all ``2 S`` rows after the last layer
    (B, 2 S, d), from the clean and the noised ids (B, S) each."""
    z = dims(cfg)
    x = params["tok_embed_weight"][jnp.concatenate([x0, xt], axis=1)]
    for i in range(z["L"]):
        x = jax.checkpoint(
            lambda x, p, i=i: block(x, p, i, z, precision))(x, params)
    return x


def forward(params, x0, xt, cfg, precision="f32"):
    """The stream of the NOISED half (B, S, d): what the head reads."""
    return trunk(params, x0, xt, cfg, precision)[:, dims(cfg)["S"]:]


def row_losses(x, labels, p, precision):
    """``-log softmax(z_i)[label_i]`` of (N, d) rows against the untied
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
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return lax.map(lambda a: rows(*a), (x.reshape(N // blk, blk, -1),
                                        labels.reshape(N // blk, blk))
                   ).reshape(N)


def losses(params, data, labels, cfg, precision="f32"):
    """``(ce, J)``: the mean over all noised rows of a masked row's
    cross-entropy at its clean token, plain and weighted by ``1 / p`` of
    its block."""
    mask_id = dims(cfg)["V"] - 1
    x0, xt = data[:, 0].astype(jnp.int32), data[:, 1].astype(jnp.int32)
    x = forward(params, x0, xt, cfg, precision)
    n = x0.size
    each = row_losses(x.reshape(n, -1), labels.reshape(n), params, precision)
    masked = (xt == mask_id).reshape(n)
    weight = data[:, 2].reshape(n)
    return (jnp.sum(jnp.where(masked, each, 0.0)) / n,
            jnp.sum(jnp.where(masked, weight * each, 0.0)) / n)


def loss(params, aux, data, labels, cfg, precision="f32"):
    """The value is ``ce`` (what the repo's ``ce`` metric reads); the
    gradient is that of ``J``, the step's objective."""
    ce, J = losses(params, data, labels, cfg, precision)
    return lax.stop_gradient(ce) + (J - lax.stop_gradient(J)), aux


# ----------------------------------------------------------------------
# the training cell's inputs: the noise is made here
# ----------------------------------------------------------------------
def data_shapes(cfg, batch):
    S = int(cfg["seq_len"])
    return (batch, 3, S), (batch * S,)


def make_batch(rng, cfg, batch):
    """``(data, labels)`` as the float32 arrays an MXNet iterator hands
    over: uniform token ids ``x0`` over the vocabulary slice less its
    last row (``MASK``), the noised copy and the row weights; the labels
    are ``x0`` (no shift)."""
    import numpy as np
    z = dims(cfg)
    S, Bk, mask_id = z["S"], z["Bk"], z["V"] - 1
    x0 = rng.integers(0, mask_id, (batch, S))
    p = (1.0 - P_FLOOR) * rng.random((batch, S // Bk)) + P_FLOOR
    p = np.repeat(p, Bk, axis=1)                # a row's block's p
    m = rng.random((batch, S)) < p
    data = np.stack([x0, np.where(m, mask_id, x0), m / p], axis=1)
    return data.astype(np.float32), \
        x0.reshape(batch * S).astype(np.float32)


def device_batch(data, labels):
    data = jnp.asarray(data, jnp.float32)
    return data, jnp.asarray(labels).astype(jnp.int32) \
        .reshape(data.shape[0], -1)


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers
# ----------------------------------------------------------------------
def pairs(cfg):
    """(query, key) pairs of one sequence and head under the mask:
    clean-clean ``S (S + Bk) / 2``, noised-clean ``S (S - Bk) / 2``,
    noised-noised ``S Bk``: ``S^2 + S Bk``."""
    z = dims(cfg)
    return z["S"] * (z["S"] + z["Bk"])


def blockdiff_attention_flops(cfg):
    """FLOPs of the cores a training step of one sequence needs, all
    layers, the MASK's pairs only, no recompute: per pair and head QK^T
    and PV forward; dV, dP, dQ, dK backward."""
    z = dims(cfg)
    return z["L"] * 2 * pairs(cfg) * z["Hq"] * 6 * z["D"]


def blockdiff_attention_bytes(cfg, bytes_per_value=2):
    """Bytes a training step of one sequence has to move for the cores:
    one read of q, k, v and one write of o over the ``2 S`` rows (the
    model's dtype), and as much again for their gradients."""
    z = dims(cfg)
    return z["L"] * 2 * 2 * z["S"] * z["D"] \
        * (2 * z["Hq"] + 2 * z["Hk"]) * bytes_per_value


def forward_flops_per_sample(cfg):
    """Multiply-adds x 2 of one sequence's forward pass, by part: the
    trunk over the ``2 S`` rows, the cores at the mask's pairs, the head
    over the ``S`` noised rows.  Routing is counted EVEN: ``top_k * held
    / num_experts`` of a row's choices reach an expert held here.
    Lookups, norms, rotary and other elementwise work are not counted."""
    z = dims(cfg)
    S, d, L = z["S"], z["d"], z["L"]
    R = 2 * S
    return {
        "projections": L * 2 * R * d * z["D"] * (2 * z["Hq"] + 2 * z["Hk"]),
        "attention": L * pairs(cfg) * 2 * z["Hq"] * 2 * z["D"],
        "router": L * 2 * R * d * z["E"],
        "experts": L * (R * z["k"] * z["held"] / z["E"]) * 3 * 2 * d * z["F"],
        "head": 2 * S * d * z["V"],
    }


def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes of one sequence of the
    configuration's length under EVEN routing, no recompute: twice the
    forward going back."""
    return 3 * sum(forward_flops_per_sample(cfg).values())


def expert_product_flops(cfg, tokens_held):
    """FLOPs, forward and backward, of the three grouped products (gate,
    up, down) for ``tokens_held`` (row, choice, layer) triples that
    reached an expert held here."""
    z = dims(cfg)
    return 3 * tokens_held * 3 * 2 * z["d"] * z["F"]
