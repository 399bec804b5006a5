"""ZAYA1 (Zyphra): a decoder whose every layer is a compressed
convolutional attention sublayer (``sym.contrib.CompressedConvAttention``)
followed by a dropless top-1 expert sublayer behind the ZAYA router
(``sym.contrib.RoutedExperts``); RMSNorm before each, one learned
per-channel scale on each branch's output, and a head tied to the
embedding.  The second language-model family of the zoo
(docs/TRAINING.md, "The dropless expert layer").

Two things make a layer more than a function of the residual stream:
the router hands its state to the next layer's router, and every expert
sublayer reports the tokens each expert got.  The counts leave the graph
behind ``BlockGrad`` as a second output, (num_layers, num_experts)
int32; output 0 is the softmax, so ``Module.fit_step``'s metric reads
what it always read.

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts``, and a token whose expert is elsewhere gets 0 from the
expert sublayer.  ``num_classes`` is the slice of the vocabulary held
here: one matrix, used by ``Embedding`` and by the head.
"""
from .. import initializer as _init
from .. import symbol as sym
from ._decoder import F32, Decoder, weight


def get_symbol(num_classes=32784, num_layers=4, d_model=2048, q_heads=8,
               kv_heads=2, head_dim=128, expert_dim=2048, num_experts=16,
               experts_held=None, router_hidden=256, conv_k0=2, conv_k1=2,
               rotary_frac=0.5, rope_theta=5e6, seq_len=8192,
               dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (positions are rotary: nothing is sized by it)."""
    d = int(d_model)
    E, R, F = int(num_experts), int(router_hidden), int(expert_dim)
    frame = Decoder(num_classes, d, E, experts_held, dtype)
    norm = frame.norm
    one = _init.One()
    fan = lambda n: _init.Normal(float(n) ** -0.5)

    # one matrix, the head's too: drawn as the other weights are
    x = frame.embed(_init.Normal(0.02))
    state, counts = None, []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        attn = sym.contrib.CompressedConvAttention(
            norm(x, pre + "attn_norm"),
            weight(pre + "attn_q_weight"), weight(pre + "attn_k_weight"),
            weight(pre + "attn_v_weight"),
            # taps sized to their fan-in, so that both convolutions
            # weigh as much as the query-key mean they are added to
            weight(pre + "attn_conv0_weight", fan(conv_k0)),
            weight(pre + "attn_conv1_weight", fan(conv_k1 * head_dim)),
            weight(pre + "attn_temp", one), weight(pre + "attn_o_weight"),
            q_heads=int(q_heads), kv_heads=int(kv_heads),
            head_dim=int(head_dim), conv_k0=int(conv_k0),
            conv_k1=int(conv_k1), rotary_frac=float(rotary_frac),
            rope_theta=float(rope_theta), name=pre + "attn")
        x = x + sym.broadcast_mul(
            attn, weight(pre + "attn_scale", one, shape=(d,)),
            name=pre + "attn_scaled")

        carry = {} if state is None else {
            "router_state": state,
            "router_carry": weight(pre + "moe_router_carry",
                                   _init.Constant(0.5), **F32)}
        moe = sym.contrib.RoutedExperts(
            norm(x, pre + "moe_norm"),
            weight(pre + "moe_router_in_weight", **F32),
            weight(pre + "moe_router_norm_gamma", one, **F32),
            weight(pre + "moe_router_fc1_weight", **F32),
            weight(pre + "moe_router_fc2_weight", **F32),
            weight(pre + "moe_router_out_weight", **F32),
            # 3-D stacks (held, out, in): Xavier would misread their fans
            weight(pre + "moe_gate_weight"), weight(pre + "moe_up_weight"),
            weight(pre + "moe_down_weight"),
            num_experts=E, held_first=frame.first, held_count=frame.held,
            num_hidden=F, router_hidden=R, carry_in=state is not None,
            name=pre + "moe", **carry)
        x = x + sym.broadcast_mul(
            moe[0], weight(pre + "moe_scale", one, shape=(d,)),
            name=pre + "moe_scaled")
        state = moe[1]
        counts.append(moe[2])
    return sym.Group(frame.close(x, counts, tied=True))
