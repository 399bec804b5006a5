"""Plain reference of the GPT-2-shaped decoder (Cerebras-GPT's shape).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
learned positions, pre-LayerNorm blocks, full multi-head causal
attention, tanh GELU, untied head with bias.  No kernel, no cache, no
batching tricks.  It imports nothing of ``mxnet_tpu`` and takes nothing
the program made: parameters come from :func:`init_leaf`, by the names a
training checkpoint of the repo's transformer uses.

Departures from the published model, both the program's and followed
here so that the comparison is of precision and not of architecture:
tanh-approximated GELU where Cerebras-GPT's ``config.json`` says
``gelu`` (erf), and an output head that is untied and has a bias.

``precision`` selects the arithmetic of every matmul operand:
``"f32"`` is the reference; ``"fp8"`` computes every matmul the way
fp8 training does (e4m3 operands forward, e5m2 gradients back, per-tensor
scales, float32 accumulation): the control, the nearest precision below
the configuration's bfloat16.
"""
import functools
import zlib

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02         # GPT-2's normal(0, 0.02)
LN_EPS = 1e-5
HI = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# parameters by name
# ----------------------------------------------------------------------
def layer_specs(cfg, i):
    d, f = int(cfg["d_model"]), int(cfg.get("ffn_dim") or 4 * cfg["d_model"])
    p = "layer%s_" % i
    return [(p + "ln1_gamma", (d,)), (p + "ln1_beta", (d,)),
            (p + "qkv_weight", (3 * d, d)), (p + "qkv_bias", (3 * d,)),
            (p + "proj_weight", (d, d)), (p + "proj_bias", (d,)),
            (p + "ln2_gamma", (d,)), (p + "ln2_beta", (d,)),
            (p + "ffn_up_weight", (f, d)), (p + "ffn_up_bias", (f,)),
            (p + "ffn_down_weight", (d, f)), (p + "ffn_down_bias", (d,))]


def embed_specs(cfg):
    d = int(cfg["d_model"])
    return [("tok_embed_weight", (int(cfg["num_classes"]), d)),
            ("pos_embed_weight", (1, int(cfg["seq_len"]), d))]


def head_specs(cfg):
    d, v = int(cfg["d_model"]), int(cfg["num_classes"])
    return [("ln_f_gamma", (d,)), ("ln_f_beta", (d,)),
            ("lm_head_weight", (v, d)), ("lm_head_bias", (v,))]


def param_specs(cfg):
    """[(name, shape)] of every parameter, in checkpoint order."""
    out = embed_specs(cfg)
    for i in range(int(cfg["num_layers"])):
        out += layer_specs(cfg, i)
    return out + head_specs(cfg)


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_kind(name):
    """How a parameter is initialised, by its name."""
    if name.endswith("_gamma"):
        return "ones"
    if name.endswith(("_bias", "_beta")):
        return "zeros"
    return "normal"


def leaf_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def leaf_value(k, kind, shape):
    """A parameter from ITS key (:func:`leaf_key`): weights normal(0,
    0.02) rounded to bfloat16 (the type they are trained and served
    in), LayerNorm gains 1, every bias and shift 0.  Float32; the
    caller casts to the type its side holds."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    w = jax.random.normal(k, shape, jnp.float32) * INIT_STD
    # reduce_precision, not a cast to bfloat16 and back: inside a compiled
    # program the TPU's compiler keeps the excess precision of such a
    # pair, and the "rounded" float32 leaf then differs from its own
    # bfloat16 copy by a rounding (PERF.md, PR 23)
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def fp8_round(x, dtype=jnp.float8_e4m3fn, top=448.0):
    """Round to an fp8 type under a per-tensor scale (amax -> top)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = top / amax
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def mm_fp8(eq, a, b):
    """A matmul the way fp8 training does it: operands rounded to e4m3
    going forward, the incoming gradient rounded to e5m2 going back,
    each under its own per-tensor scale; accumulation in float32."""
    return _einsum(eq, fp8_round(a), fp8_round(b))


def _mm_fp8_fwd(eq, a, b):
    qa, qb = fp8_round(a), fp8_round(b)
    return _einsum(eq, qa, qb), (qa, qb)


def _mm_fp8_bwd(eq, res, g):
    _, vjp = jax.vjp(functools.partial(_einsum, eq), *res)
    return vjp(fp8_round(g, jnp.float8_e5m2, 57344.0))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(eq, a, b, precision):
    if precision == "f32":
        return _einsum(eq, a, b)
    if precision == "fp8":
        return mm_fp8(eq, a, b)
    raise ValueError("precision %r (f32 or fp8)" % (precision,))


def layer_norm(x, gamma, beta):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def embed(tokens, p):
    """(B, S) int tokens -> (B, S, d); positions 0..S-1."""
    S = tokens.shape[1]
    return p["tok_embed_weight"][tokens] + p["pos_embed_weight"][0, :S][None]


def block(x, p, pre, num_heads, precision="f32"):
    """One pre-LN block on (B, S, d)."""
    B, S, d = x.shape
    H = int(num_heads)
    D = d // H
    h = layer_norm(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
    qkv = _mm("bsd,ed->bse", h, p[pre + "qkv_weight"], precision) \
        + p[pre + "qkv_bias"]
    q, k, v = (qkv[..., j * d:(j + 1) * d].reshape(B, S, H, D)
               for j in range(3))
    s = _mm("bqhe,bkhe->bhqk", q, k, precision) / (D ** 0.5)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask, s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhe->bqhe", a, v, precision).reshape(B, S, d)
    x = x + _mm("bsd,ed->bse", o, p[pre + "proj_weight"], precision) \
        + p[pre + "proj_bias"]
    h = layer_norm(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
    u = _mm("bsd,fd->bsf", h, p[pre + "ffn_up_weight"], precision) \
        + p[pre + "ffn_up_bias"]
    u = jax.nn.gelu(u, approximate=True)
    return x + _mm("bsf,df->bsd", u, p[pre + "ffn_down_weight"], precision) \
        + p[pre + "ffn_down_bias"]


def head(x, p, precision="f32"):
    """(..., d) -> (..., vocab) logits."""
    h = layer_norm(x, p["ln_f_gamma"], p["ln_f_beta"])
    return _mm("...d,vd->...v", h, p["lm_head_weight"], precision) \
        + p["lm_head_bias"]


def loss(params, aux, tokens, labels, cfg, precision="f32"):
    """Mean next-token cross-entropy over every position of the batch:
    what the repo's ``ce`` metric reads and what SoftmaxOutput with
    ``normalization='batch'`` differentiates.  ``aux`` (none here) is
    returned as it came: the training reference's common signature."""
    x = embed(tokens, params)
    for i in range(int(cfg["num_layers"])):
        x = jax.checkpoint(
            lambda x, p, pre="layer%d_" % i: block(
                x, p, pre, cfg["num_heads"], precision))(x, params)
    logits = head(x, params, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked), aux


# ----------------------------------------------------------------------
# the training cells' inputs
# ----------------------------------------------------------------------
def data_shapes(cfg, batch):
    S = int(cfg["seq_len"])
    return (batch, S), (batch * S,)


def make_batch(rng, cfg, batch):
    """Uniform token ids (every row different) and their next-token
    labels, as the float32 arrays an MXNet iterator hands over."""
    import numpy as np
    S, V = int(cfg["seq_len"]), int(cfg["num_classes"])
    tok = rng.integers(0, V, (batch, S))
    lab = np.roll(tok, -1, axis=1).reshape(batch * S)
    return tok.astype(np.float32), lab.astype(np.float32)


def device_batch(data, labels):
    """The reference's view of a host batch: int tokens (B, S) and
    labels (B, S)."""
    tok = jnp.asarray(data).astype(jnp.int32)
    return tok, jnp.asarray(labels).astype(jnp.int32).reshape(tok.shape)


# ----------------------------------------------------------------------
# the serving cells' check: one full forward over prompt + served tokens
# ----------------------------------------------------------------------
_GENERIC = "layerX_"


def served_token_gaps(key, cfg, rows, pick_len, precision="f32",
                      pad_to=128):
    """The reference's logits at the positions that produced each served
    token.

    ``rows``: [(prompt tokens, served tokens)].  Every sequence (prompt
    + served) runs once through the full forward, padded on the right to
    one length (causal attention: padding changes nothing before it), a
    layer at a time with that layer's weights made again from the seed
    in float32, so a whole float32 model never sits on the device.
    Returns ``(logits, width)``: float32 (len(rows), pick_len, vocab)
    logits of the positions prompt_len-1 ... that predict the served
    tokens (rows beyond a request's served length are padding), as a
    device array."""
    import numpy as np
    H = int(cfg["num_heads"])
    seqs = [list(p) + list(s) for p, s in rows]
    T = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    T = min(T, int(cfg["seq_len"]))
    tokens = np.zeros((len(seqs), T), np.int32)
    pick = np.zeros((len(seqs), pick_len), np.int32)
    for i, ((p, s), seq) in enumerate(zip(rows, seqs)):
        tokens[i, :len(seq)] = seq
        n = min(len(s), pick_len)
        pick[i, :n] = np.arange(len(p) - 1, len(p) - 1 + n)

    def keys_of(specs):
        return {n: leaf_key(key, n) for n, _ in specs}

    @jax.jit
    def start(tokens, ks):
        p = {n: leaf_value(ks[n], leaf_kind(n), s)
             for n, s in embed_specs(cfg)}
        return embed(tokens, p)

    @jax.jit
    def layer(x, ks):
        p = {n: leaf_value(ks[n], leaf_kind(n), s)
             for n, s in layer_specs(cfg, "X")}
        return block(x, p, _GENERIC, H, precision)

    @jax.jit
    def finish(x, pick, ks):
        p = {n: leaf_value(ks[n], leaf_kind(n), s)
             for n, s in head_specs(cfg)}
        picked = jnp.take_along_axis(x, pick[..., None], axis=1)
        return head(picked, p, precision)

    x = start(jnp.asarray(tokens), keys_of(embed_specs(cfg)))
    generic = [n for n, _ in layer_specs(cfg, "X")]
    for i in range(int(cfg["num_layers"])):
        real = [n for n, _ in layer_specs(cfg, i)]
        x = layer(x, {g: leaf_key(key, r) for g, r in zip(generic, real)})
    return finish(x, jnp.asarray(pick), keys_of(head_specs(cfg)))


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers (benchmark/counts.py)
# ----------------------------------------------------------------------
def train_flops_per_sample(cfg):
    """FLOPs of the forward and backward passes of one sequence of the
    configuration's length, no recompute."""
    import counts
    S = int(cfg["seq_len"])
    return counts.lm_train_flops_per_token(cfg, S) * S


def serve_iter_bytes(cfg, live_rows, tokens_in_iter):
    """Bytes one engine iteration must read."""
    import counts
    return counts.lm_serve_iter_bytes(cfg, live_rows, tokens_in_iter)
