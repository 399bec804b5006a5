"""Kimi-Linear (Moonshot AI; ``model_type`` ``kimi_linear``,
arXiv:2510.26692): a decoder whose layers are of two kinds named by two
published lists, ``kda_layers`` and ``full_attn_layers`` (1-based, three
to one).  A KDA layer mixes tokens by Kimi Delta Attention
(``sym.contrib.KimiDeltaAttention``: a delta rule whose forget gate is
one value a key channel); a full layer by multi-head latent attention
with NO position (``sym.contrib.LatentAttention`` with
``rotary=False``: the linear layers carry the order).  The first
``dense_layers`` layers feed forward through a dense SiLU-gated FFN
(``sym.contrib.GatedFFN``) behind whichever mixer the lists give them
(a KDA mixer, where Kanana-2's dense layer has latent attention); every
later one through a dropless top-k expert sublayer behind independent
sigmoid scores with a selection bias, beside an ungated shared expert
(``sym.contrib.RoutedExperts`` with ``router="sigmoid"``).  RMSNorm
before each sublayer, an untied head.  The eighth language-model family
of the zoo (docs/TRAINING.md, "The eighth family").

The selection bias of each expert sublayer is an AUXILIARY state
(``layerN_moe_router_bias``, float32), as ``models/kanana2.py``'s.

Every expert sublayer reports the (token, choice) pairs each expert
got.  The counts leave the graph behind ``BlockGrad`` as a second
output, (expert layers, num_experts) int32; output 0 is the softmax.

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

# the source's lists at its 27 layers
KDA_LAYERS = tuple(i for i in range(1, 27) if i % 4)
FULL_ATTN_LAYERS = tuple(range(4, 28, 4)) + (27,)


def layer_kinds(num_layers, kda_layers=KDA_LAYERS,
                full_attn_layers=FULL_ATTN_LAYERS):
    """``["kda" | "full", ...]``, one a layer, from the two published
    1-based lists cut to ``num_layers``.  A layer both lists name is
    full (the source's last layer is in both); one neither names is an
    error."""
    kda, full = set(map(int, kda_layers)), set(map(int, full_attn_layers))
    kinds = []
    for i in range(1, int(num_layers) + 1):
        if i not in kda and i not in full:
            raise ValueError("layer %d is in neither kda_layers nor "
                             "full_attn_layers" % i)
        kinds.append("full" if i in full else "kda")
    return kinds


def get_symbol(num_classes=20480, num_layers=5, d_model=2304, heads=32,
               head_dim=128, conv_kernel=4, nope_dim=128, rope_dim=64,
               v_dim=128, kv_rank=512, kda_layers=KDA_LAYERS,
               full_attn_layers=FULL_ATTN_LAYERS, dense_layers=1,
               dense_dim=9216, expert_dim=1024, num_experts=256,
               experts_held=None, top_k=8, route_scale=2.446,
               shared_dim=1024, seq_len=8192, dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (no layer has a position: nothing is sized by it)."""
    E, F, Fs = int(num_experts), int(expert_dim), int(shared_dim)
    H, eps = int(heads), 1e-5
    zero, one = _init.Zero(), _init.One()
    # norms mirrored: their float32 intermediates are made again from
    # the stream in the backward pass
    frame = Decoder(num_classes, d_model, E, experts_held, dtype, eps=eps,
                    force_mirroring=True)
    norm = frame.norm
    if not 0 <= int(dense_layers) < int(num_layers):
        raise ValueError("dense_layers=%r of %r layers leaves no expert "
                         "layer" % (dense_layers, num_layers))

    x = frame.embed()
    counts = []
    for i, kind in enumerate(layer_kinds(num_layers, kda_layers,
                                         full_attn_layers)):
        pre = "layer%d_" % i
        h = norm(x, pre + "in_norm")
        if kind == "kda":
            x = x + sym.contrib.KimiDeltaAttention(
                h, weight(pre + "kda_q_weight"), weight(pre + "kda_k_weight"),
                weight(pre + "kda_v_weight"), weight(pre + "kda_conv_weight"),
                weight(pre + "kda_fa_weight"), weight(pre + "kda_fb_weight"),
                # the released layer draws A in (1, 16) and the step dt
                # log-uniformly; a seeded run sets both by name
                weight(pre + "kda_A_log", zero, **F32),
                weight(pre + "kda_dt_bias", one, **F32),
                weight(pre + "kda_b_weight"), weight(pre + "kda_ga_weight"),
                weight(pre + "kda_gb_weight"),
                weight(pre + "kda_gb_bias", zero),
                weight(pre + "kda_norm_gamma", one),
                weight(pre + "kda_o_weight"),
                heads=H, head_dim=int(head_dim),
                conv_kernel=int(conv_kernel), eps=eps, name=pre + "kda")
        else:
            x = x + sym.contrib.LatentAttention(
                h, weight(pre + "attn_q_weight"),
                weight(pre + "attn_kva_weight"),
                weight(pre + "attn_kv_norm_gamma", one),
                weight(pre + "attn_kvb_weight"),
                weight(pre + "attn_o_weight"),
                heads=H, nope_dim=int(nope_dim), rope_dim=int(rope_dim),
                v_dim=int(v_dim), kv_rank=int(kv_rank), eps=eps,
                rotary=False, name=pre + "attn")

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
            router_bias=weight(pre + "moe_router_bias", zero, **F32),
            router="sigmoid", top_k=int(top_k),
            route_scale=float(route_scale), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            shared_hidden=Fs, shared_gate=False, name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])
    return sym.Group(frame.close(x, counts))
