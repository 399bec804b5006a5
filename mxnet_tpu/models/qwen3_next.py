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
from ..telemetry.moe import COUNTS_NODE     # the counts' node (output 1)


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
    kinds = layer_kinds(num_layers, full_attention_interval)
    low = dtype in ("float16", "bfloat16")
    std = _init.Normal(0.02)
    zero, one = _init.Zero(), _init.One()
    f32 = {"dtype": "float32"}      # the router and the decay, whatever dtype
    eps = 1e-6

    def weight(name, init=std, **kw):
        return sym.Variable(name, init=init, **kw)

    def norm(x, name):
        return sym.RMSNorm(x, gamma=weight(name + "_gamma", zero), eps=eps,
                           zero_centered=True, name=name)

    data = sym.Variable("data")                      # (B, S) token ids
    embed = weight("tok_embed_weight", _init.Normal(1.0),
                   shape=(vocab, d), **f32)
    x = sym.Embedding(data, weight=embed, input_dim=vocab, output_dim=d,
                      name="tok_embed")
    if low:
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")

    counts = []
    for i, kind in enumerate(kinds):
        pre = "layer%d_" % i
        h = norm(x, pre + "in_norm")
        if kind == "linear":
            mixed = sym.contrib.GatedDeltaNet(
                h, weight(pre + "gdn_qkvz_weight"),
                weight(pre + "gdn_ba_weight"),
                weight(pre + "gdn_conv_weight"),
                # the Gated DeltaNet reference draws A in (0, 16) and the
                # step dt log-uniformly; a seeded run sets both by name
                weight(pre + "gdn_A_log", zero, **f32),
                weight(pre + "gdn_dt_bias", one, **f32),
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

        h = norm(x, pre + "post_norm")
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
            shared_sg_weight=weight(pre + "moe_shared_sg_weight"),
            router="linear", top_k=int(top_k), num_experts=E,
            held_first=first, held_count=held, num_hidden=F,
            shared_hidden=Fs, name=pre + "moe")
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
