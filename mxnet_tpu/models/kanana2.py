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
from ._decoder import F32, Decoder, weight


def get_symbol(num_classes=16032, num_layers=5, d_model=2048, heads=32,
               nope_dim=128, rope_dim=64, v_dim=128, kv_rank=512,
               rope_theta=1e6, dense_layers=1, dense_dim=6144,
               expert_dim=768, num_experts=128, experts_held=None, top_k=6,
               route_scale=2.448, shared_dim=1536, seq_len=8192,
               dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (positions are rotary: nothing is sized by it)."""
    E, F, Fs = int(num_experts), int(expert_dim), int(shared_dim)
    eps = 1e-6
    frame = Decoder(num_classes, d_model, E, experts_held, dtype, eps=eps)
    norm = frame.norm
    if not 0 <= int(dense_layers) < int(num_layers):
        raise ValueError("dense_layers=%r of %r layers leaves no expert "
                         "layer" % (dense_layers, num_layers))

    x = frame.embed()
    counts = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        x = x + sym.contrib.LatentAttention(
            norm(x, pre + "in_norm"), weight(pre + "attn_q_weight"),
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
            router_weight=weight(pre + "moe_router_weight", **F32),
            shared_gate_weight=weight(pre + "moe_shared_gate_weight"),
            shared_up_weight=weight(pre + "moe_shared_up_weight"),
            shared_down_weight=weight(pre + "moe_shared_down_weight"),
            router_bias=weight(pre + "moe_router_bias", _init.Zero(), **F32),
            router="sigmoid", top_k=int(top_k),
            route_scale=float(route_scale), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            shared_hidden=Fs, shared_gate=False, name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])
    return sym.Group(frame.close(x, counts))
