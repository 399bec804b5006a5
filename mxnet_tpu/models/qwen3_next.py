"""Qwen3-Next (Qwen): a decoder whose layers are of two kinds in a
published order, three Gated DeltaNet linear-attention mixers
(``sym.contrib.GatedDeltaNet``) to every gated softmax-attention mixer
(``sym.contrib.GatedCausalSelfAttention``), each followed by a dropless
top-k expert sublayer with one shared expert behind the plain softmax
router (``sym.contrib.RoutedExperts`` with ``router="linear"``);
zero-centred RMSNorm before each, and an untied head.  The third
language-model family of the zoo (docs/TRAINING.md, "The third family").

Every expert sublayer reports the (token, choice) pairs each expert
got.  The counts leave the graph behind ``BlockGrad`` as a second
output, (num_layers, num_experts) int32, as ``models/zaya.py``'s do;
output 0 is the softmax.

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts`` and normalises a token's weights over all ``top_k``, and
a choice whose expert is elsewhere adds 0.  ``num_classes`` is the slice
of the vocabulary held here, in the embedding and in the head.
"""
from .. import initializer as _init
from .. import symbol as sym
from ._decoder import F32, Decoder, weight


def layer_kinds(num_layers, full_attention_interval=4):
    """``["linear" | "full", ...]``, one a layer, in the published
    order: layer i is full when (i + 1) % full_attention_interval == 0."""
    every = int(full_attention_interval)
    if every < 1:
        raise ValueError("full_attention_interval=%r" % (full_attention_interval,))
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(int(num_layers))]


def get_symbol(num_classes=18992, num_layers=4, d_model=2048,
               full_attention_interval=4, q_heads=16, kv_heads=2,
               head_dim=256, rotary_frac=0.25, rope_theta=1e7,
               gdn_k_heads=16, gdn_v_heads=32, gdn_k_dim=128, gdn_v_dim=128,
               conv_kernel=4, expert_dim=512, num_experts=512,
               experts_held=None, top_k=10, shared_dim=512, seq_len=8192,
               dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (positions are rotary: nothing is sized by it)."""
    E, F, Fs = int(num_experts), int(expert_dim), int(shared_dim)
    zero, one = _init.Zero(), _init.One()
    eps = 1e-6
    frame = Decoder(num_classes, d_model, E, experts_held, dtype,
                    norm_init=zero, eps=eps, zero_centered=True)
    norm = frame.norm

    x = frame.embed()
    counts = []
    for i, kind in enumerate(layer_kinds(num_layers,
                                         full_attention_interval)):
        pre = "layer%d_" % i
        h = norm(x, pre + "in_norm")
        if kind == "linear":
            mixed = sym.contrib.GatedDeltaNet(
                h, weight(pre + "gdn_qkvz_weight"),
                weight(pre + "gdn_ba_weight"),
                weight(pre + "gdn_conv_weight"),
                # the Gated DeltaNet reference draws A in (0, 16) and the
                # step dt log-uniformly; a seeded run sets both by name
                weight(pre + "gdn_A_log", zero, **F32),
                weight(pre + "gdn_dt_bias", one, **F32),
                weight(pre + "gdn_norm_gamma", one),
                weight(pre + "gdn_out_weight"),
                k_heads=int(gdn_k_heads), v_heads=int(gdn_v_heads),
                k_dim=int(gdn_k_dim), v_dim=int(gdn_v_dim),
                conv_kernel=int(conv_kernel), eps=eps, name=pre + "gdn")
        else:
            mixed = sym.contrib.GatedCausalSelfAttention(
                h, weight(pre + "attn_q_weight"),
                weight(pre + "attn_k_weight"), weight(pre + "attn_v_weight"),
                weight(pre + "attn_q_norm_gamma", zero),
                weight(pre + "attn_k_norm_gamma", zero),
                weight(pre + "attn_o_weight"),
                q_heads=int(q_heads), kv_heads=int(kv_heads),
                head_dim=int(head_dim), rotary_frac=float(rotary_frac),
                rope_theta=float(rope_theta), eps=eps, name=pre + "attn")
        x = x + mixed

        moe = sym.contrib.RoutedExperts(
            norm(x, pre + "post_norm"),
            # 3-D stacks (held, out, in): Xavier would misread their fans
            gate_weight=weight(pre + "moe_gate_weight"),
            up_weight=weight(pre + "moe_up_weight"),
            down_weight=weight(pre + "moe_down_weight"),
            router_weight=weight(pre + "moe_router_weight", **F32),
            shared_gate_weight=weight(pre + "moe_shared_gate_weight"),
            shared_up_weight=weight(pre + "moe_shared_up_weight"),
            shared_down_weight=weight(pre + "moe_shared_down_weight"),
            shared_sg_weight=weight(pre + "moe_shared_sg_weight"),
            router="linear", top_k=int(top_k), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            shared_hidden=Fs, name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])
    return sym.Group(frame.close(x, counts))
