"""Keye-VL-2.0's language model (Kwai; a Qwen3-MoE block whose
attention is DeepSeek Sparse Attention): a decoder whose every layer
mixes tokens by grouped-query attention over the keys that a learned
index scorer picks for each query (``sym.contrib.SparseIndexedAttention``:
16 scorer heads of 64 against one shared key a token, the 2048 best
keys a query) and feeds forward through a dropless top-k expert
sublayer behind a softmax router (``sym.contrib.RoutedExperts`` with
``router="linear"``, no shared expert).  RMSNorm before each sublayer,
an untied head.  The fifth language-model family of the zoo
(docs/TRAINING.md, "The fifth family").  The vision tower is not built:
on text the three multimodal position ids agree, and the rotary
position is the plain one.

Two objectives, one step.  The scorer reads the normalised stream
behind a stop-gradient and its choice carries none, so the language
model's loss never reaches the scorer's five leaves a layer
(``layerN_attn_idx_*``).  They are trained by the index loss, the mean
over tokens of ``KL(p_t || softmax over the chosen keys of the scorer's
scores)`` with ``p_t`` the main attention's own head-mean probabilities
as a constant, which in turn reaches nothing else.  The layers' index
losses are summed behind ``MakeLoss`` (weight 1): output 1, a second
loss head beside the softmax (output 0, what ``ce`` reads).

Output 2 is the experts' token counts, (layers, num_experts) int32, as
``models/qwen3_next.py``'s; output 3 the sparse attention's live score
tiles, (layers, 2) int32 (``telemetry/dsa.py``); both behind
``BlockGrad``.

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts`` and normalises a token's weights over all ``top_k``,
and a choice whose expert is elsewhere adds 0.  ``num_classes`` is the
slice of the vocabulary held here, in the embedding and in the head.
"""
from .. import initializer as _init
from .. import symbol as sym
from ..telemetry.dsa import TILES_NODE      # the tiles' node (output 3)
from ._decoder import F32, Decoder, weight

INDEX_LOSS_NODE = "index_loss"              # the second loss head (output 1)


def get_symbol(num_classes=18992, num_layers=4, d_model=2048, q_heads=32,
               kv_heads=4, head_dim=128, rope_theta=1e7, idx_heads=16,
               idx_dim=64, topk=2048, q_chunk=512, kv_chunk=512,
               expert_dim=768, num_experts=128, experts_held=None, top_k=8,
               seq_len=16384, dtype="float32", **kwargs):
    """``seq_len`` is accepted for factory-signature parity with the
    transformer (positions are rotary: nothing is sized by it)."""
    E, F = int(num_experts), int(expert_dim)
    eps = 1e-6
    # norms mirrored: at 16 384 tokens a norm's float32 intermediates
    # are 0.4 GB that the backward pass can make again from the stream
    frame = Decoder(num_classes, d_model, E, experts_held, dtype, eps=eps,
                    force_mirroring=True)
    norm = frame.norm

    x = frame.embed()
    counts, tiles, index_losses = [], [], []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        attn = sym.contrib.SparseIndexedAttention(
            norm(x, pre + "in_norm"),
            weight(pre + "attn_q_weight"), weight(pre + "attn_k_weight"),
            weight(pre + "attn_v_weight"),
            weight(pre + "attn_q_norm_gamma", _init.One()),
            weight(pre + "attn_k_norm_gamma", _init.One()),
            weight(pre + "attn_o_weight"),
            weight(pre + "attn_idx_q_weight", **F32),
            weight(pre + "attn_idx_k_weight", **F32),
            weight(pre + "attn_idx_w_weight", **F32),
            weight(pre + "attn_idx_k_norm_gamma", _init.One(), **F32),
            weight(pre + "attn_idx_k_norm_beta", _init.Zero(), **F32),
            q_heads=int(q_heads), kv_heads=int(kv_heads),
            head_dim=int(head_dim), idx_heads=int(idx_heads),
            idx_dim=int(idx_dim), topk=int(topk),
            rope_theta=float(rope_theta), eps=eps, q_chunk=int(q_chunk),
            kv_chunk=int(kv_chunk), name=pre + "attn")
        x = x + attn[0]
        index_losses.append(attn[1])
        tiles.append(attn[2])

        moe = sym.contrib.RoutedExperts(
            norm(x, pre + "post_norm"),
            # 3-D stacks (held, out, in): Xavier would misread their fans
            gate_weight=weight(pre + "moe_gate_weight"),
            up_weight=weight(pre + "moe_up_weight"),
            down_weight=weight(pre + "moe_down_weight"),
            router_weight=weight(pre + "moe_router_weight", **F32),
            router="linear", top_k=int(top_k), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            name=pre + "moe")
        x = x + moe[0]
        counts.append(moe[2])

    out, tokens = frame.close(x, counts)
    index_loss = sym.MakeLoss(
        sym.add_n(*index_losses, name="index_loss_sum"),
        name=INDEX_LOSS_NODE)
    live = sym.BlockGrad(sym.stack(*tiles, axis=0, name="dsa_tiles_all"),
                         name=TILES_NODE)
    return sym.Group([out, index_loss, tokens, live])
