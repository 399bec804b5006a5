"""SmallThinker's language model (PowerInfer; SmallThinker-21BA3B): a
decoder whose layers mix tokens by plain grouped-query attention of two
kinds in one model, read a layer at a time from two lists of the
source's config: ``window_layout[l] == 1`` is a layer whose queries
attend the last ``window`` keys, ``0`` one that attends every earlier
key; ``rope_layout[l] == 1`` turns its queries and keys by rotary
position, ``0`` gives it no position at all (the published lists have
period 4: a full layer without position, then three window layers with
it).  Both are ``sym.contrib.GroupedQueryAttention``, each layer with
its own attributes.

Every layer feeds forward through a dropless top-k expert sublayer
behind a softmax router (``sym.contrib.RoutedExperts`` with
``router="linear"``, ReLU-gated experts, no shared expert) whose router
reads the layer's INPUT, the residual stream before the attention
sublayer and before any norm, while the experts read the normalised
stream after it: the router's gradient joins the stream at the layer's
input.  RMSNorm before each sublayer, an untied head.  The sixth
language-model family of the zoo (docs/TRAINING.md, "The sixth family").

Output 1 is the experts' token counts, (layers, num_experts) int32,
behind ``BlockGrad`` (``telemetry/moe.py``).

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts`` and normalises a token's weights over all ``top_k``,
and a choice whose expert is elsewhere adds 0.  ``num_classes`` is the
slice of the vocabulary held here, in the embedding and in the head.
"""
from .. import symbol as sym
from ._decoder import F32, Decoder, weight


def get_symbol(num_classes=18992, num_layers=4, d_model=2560, q_heads=28,
               kv_heads=4, head_dim=128, rope_theta=1.5e6, window=4096,
               window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
               expert_dim=768, num_experts=64, experts_held=None, top_k=6,
               seq_len=16384, dtype="float32", **kwargs):
    """``window_layout`` and ``rope_layout`` give each layer its kind and
    are as long as the model is deep.  ``seq_len`` is accepted for
    factory-signature parity with the transformer (positions are rotary
    or absent: nothing is sized by it)."""
    L, E, F = int(num_layers), int(num_experts), int(expert_dim)
    if len(window_layout) != L or len(rope_layout) != L:
        raise ValueError("window_layout (%d) and rope_layout (%d) each name "
                         "every one of the %d layers"
                         % (len(window_layout), len(rope_layout), L))
    # norms mirrored: at 16 384 tokens a norm's float32 intermediates
    # are 0.5 GB that the backward pass can make again from the stream
    frame = Decoder(num_classes, d_model, E, experts_held, dtype, eps=1e-6,
                    force_mirroring=True)
    norm = frame.norm

    x = frame.embed()
    counts = []
    for i in range(L):
        pre = "layer%d_" % i
        layer_in = x                # what the router reads
        attn = sym.contrib.GroupedQueryAttention(
            norm(x, pre + "in_norm"), weight(pre + "attn_q_weight"),
            weight(pre + "attn_k_weight"), weight(pre + "attn_v_weight"),
            weight(pre + "attn_o_weight"), q_heads=int(q_heads),
            kv_heads=int(kv_heads), head_dim=int(head_dim),
            window=int(window) if int(window_layout[i]) else 0,
            rotary=bool(int(rope_layout[i])), rope_theta=float(rope_theta),
            name=pre + "attn")
        x = x + attn

        moe = sym.contrib.RoutedExperts(
            norm(x, pre + "post_norm"),
            # 3-D stacks (held, out, in): Xavier would misread their fans
            gate_weight=weight(pre + "moe_gate_weight"),
            up_weight=weight(pre + "moe_up_weight"),
            down_weight=weight(pre + "moe_down_weight"),
            router_weight=weight(pre + "moe_router_weight", **F32),
            router_data=layer_in, router_stream=True,
            router="linear", act="relu", top_k=int(top_k), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])
    return sym.Group(frame.close(x, counts))
