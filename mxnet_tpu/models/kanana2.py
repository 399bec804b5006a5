"""Kanana-2 (Kakao; ``model_type`` ``deepseek_v3``): a decoder whose every
layer mixes tokens by multi-head latent attention
(``sym.contrib.LatentAttention``: keys and values made from one
512-wide latent a token, queries and keys 192 wide with position on 64
of them, values 128).  The first ``dense_layers`` layers feed forward
through a dense SiLU-gated FFN (``sym.contrib.GatedFFN``); every later
one through a dropless top-k expert sublayer behind independent sigmoid
scores with a selection bias, beside an ungated shared expert
(``sym.contrib.RoutedExperts`` with ``router="sigmoid"``).  RMSNorm
before each sublayer, an untied head.  The fourth language-model family
of the zoo (docs/TRAINING.md, "The fourth family").

The selection bias of each expert sublayer is an AUXILIARY state
(``layerN_moe_router_bias``, float32): it joins a token's scores for
the choice of its experts only, takes no gradient, has no optimizer
state, and a fit step leaves it as it came.  (The rule that moves it by
the experts' load belongs to the training recipe, not the published
architecture; it is not built.)

Every expert sublayer reports the (token, choice) pairs each expert
got.  The counts leave the graph behind ``BlockGrad`` as a second
output, (expert layers, num_experts) int32, as ``models/qwen3_next.py``'s
do; output 0 is the softmax.

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts``, normalises a token's weights over all ``top_k`` and
scales them by ``route_scale``, and a choice whose expert is elsewhere
adds 0.  ``num_classes`` is the slice of the vocabulary held here, in
the embedding and in the head.
"""
from .. import initializer as _init
from .. import symbol as sym
from ..telemetry.moe import COUNTS_NODE     # the counts' node (output 1)


def get_symbol(num_classes=16032, num_layers=5, d_model=2048, heads=32,
               nope_dim=128, rope_dim=64, v_dim=128, kv_rank=512,
               rope_theta=1e6, dense_layers=1, dense_dim=6144,
               expert_dim=768, num_experts=128, experts_held=None, top_k=6,
               route_scale=2.448, shared_dim=1536, seq_len=8192,
               dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (positions are rotary: nothing is sized by it)."""
    vocab, d = int(num_classes), int(d_model)
    E, F, Fs = int(num_experts), int(expert_dim), int(shared_dim)
    if experts_held is None:
        first, held = 0, E
    elif isinstance(experts_held, int):
        first, held = 0, int(experts_held)
    else:
        first, held = (int(v) for v in experts_held)
    if not (0 <= first and 0 < held and first + held <= E):
        raise ValueError("experts_held=%r is no part of %d experts"
                         % (experts_held, E))
    if not 0 <= int(dense_layers) < int(num_layers):
        raise ValueError("dense_layers=%r of %r layers leaves no expert "
                         "layer" % (dense_layers, num_layers))
    low = dtype in ("float16", "bfloat16")
    std = _init.Normal(0.02)
    f32 = {"dtype": "float32"}      # the router, whatever dtype
    eps = 1e-6

    def weight(name, init=std, **kw):
        return sym.Variable(name, init=init, **kw)

    def norm(x, name):
        return sym.RMSNorm(x, gamma=weight(name + "_gamma", _init.One()),
                           eps=eps, name=name)

    data = sym.Variable("data")                      # (B, S) token ids
    embed = weight("tok_embed_weight", _init.Normal(1.0),
                   shape=(vocab, d), **f32)
    x = sym.Embedding(data, weight=embed, input_dim=vocab, output_dim=d,
                      name="tok_embed")
    if low:
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")

    counts = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        h = norm(x, pre + "in_norm")
        x = x + sym.contrib.LatentAttention(
            h, weight(pre + "attn_q_weight"),
            weight(pre + "attn_kva_weight"),
            weight(pre + "attn_kv_norm_gamma", _init.One()),
            weight(pre + "attn_kvb_weight"), weight(pre + "attn_o_weight"),
            heads=int(heads), nope_dim=int(nope_dim), rope_dim=int(rope_dim),
            v_dim=int(v_dim), kv_rank=int(kv_rank),
            rope_theta=float(rope_theta), eps=eps, name=pre + "attn")

        h = norm(x, pre + "post_norm")
        if i < int(dense_layers):
            x = x + sym.contrib.GatedFFN(
                h, weight(pre + "ffn_gate_weight"),
                weight(pre + "ffn_up_weight"),
                weight(pre + "ffn_down_weight"),
                num_hidden=int(dense_dim), name=pre + "ffn")
            continue
        moe = sym.contrib.RoutedExperts(
            h,
            # 3-D stacks (held, out, in): Xavier would misread their fans
            gate_weight=weight(pre + "moe_gate_weight"),
            up_weight=weight(pre + "moe_up_weight"),
            down_weight=weight(pre + "moe_down_weight"),
            router_weight=weight(pre + "moe_router_weight", **f32),
            shared_gate_weight=weight(pre + "moe_shared_gate_weight"),
            shared_up_weight=weight(pre + "moe_shared_up_weight"),
            shared_down_weight=weight(pre + "moe_shared_down_weight"),
            router_bias=weight(pre + "moe_router_bias", _init.Zero(), **f32),
            router="sigmoid", top_k=int(top_k),
            route_scale=float(route_scale), num_experts=E,
            held_first=first, held_count=held, num_hidden=F,
            shared_hidden=Fs, shared_gate=False, name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])

    x = norm(x, "final_norm")
    logits = sym.FullyConnected(data=x, weight=weight("lm_head_weight"),
                                no_bias=True, num_hidden=vocab,
                                flatten=False, name="lm_head")
    if low:
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    flat = sym.Reshape(data=logits, shape=(-1, vocab), name="logits_2d")
    out = sym.SoftmaxOutput(data=flat, name="softmax",
                            normalization="batch")
    tokens = sym.BlockGrad(sym.stack(*counts, axis=0, name="moe_tokens_all"),
                           name=COUNTS_NODE)
    return sym.Group([out, tokens])
