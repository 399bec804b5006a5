"""SDAR's language model (JetLM; SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``): a Qwen3-MoE block trained by block diffusion.  Every
layer mixes tokens by grouped-query attention with per-head RMS norms of
queries and keys (``sym.contrib.GroupedQueryAttention`` with
``qk_norm``) and feeds forward through a dropless top-k expert sublayer
behind a softmax router (``sym.contrib.RoutedExperts`` with
``router="linear"``, no shared expert).  RMSNorm before each sublayer,
an untied head.  The seventh language-model family of the zoo
(docs/TRAINING.md, "The seventh family").

One training pass holds a sequence ``x0`` of ``L = seq_len`` tokens and
its noised copy ``xt`` (a masked token is ``MASK``, the slice's last
row) side by side, ``2 L`` rows: row ``L + i`` stands at position ``i``,
and the attention's mask is the block-diffusion one (``blocks``, the
block length): a clean row sees the clean blocks up to its own, a noised
row the clean blocks before its own and the noised rows of its own
block.  Logits are taken of the noised half only, and the objective is
the masked, weighted denoising loss ``(1 / L) sum_i m_i w_i ce_i`` with
``w_i = 1 / p`` of the row's block (``sym.contrib.DiffusionHead``
between ``lm_head`` and the loss head: the ``ce`` metric reads ``(1 / L)
sum_i m_i ce_i``, the gradient is the objective's, and the head stays
deferred).

The residual stream is float32 whatever ``dtype`` is, and each router
reads the normalised float32 rows (the masked rows carry one embedding
and lie within a bfloat16 rounding of one another: ``get_symbol``); the
expert sublayer's sorted rows hold twice the even share
(``rows_slack``), because rows that are alike choose alike.

``data`` is one float32 array (B, 3, L): ``x0``, ``xt`` and the row
weight ``m_i / p_blk(i)``; ``softmax_label`` is ``x0`` (B * L), the
token at the row itself (no shift).

Output 1 is the experts' token counts, (layers, num_experts) int32,
over the 2 L rows; output 2 the head's ``(masked rows, rows)`` int32
(``telemetry/diffusion.py``); both behind ``BlockGrad``.

``experts_held`` is the chip's share of a layer's experts (how many,
from expert 0, or ``[first, count]``): the router still scores all
``num_experts`` and normalises a token's weights over all ``top_k``,
and a choice whose expert is elsewhere adds 0.  ``num_classes`` is the
slice of the vocabulary held here, in the embedding and in the head;
``MASK`` is its last row and ids are drawn from the rows before it.
"""
from .. import initializer as _init
from .. import symbol as sym
from ..telemetry.diffusion import ROWS_NODE     # the rows' node (output 2)
from ._decoder import F32, Decoder, weight


def get_symbol(num_classes=18992, num_layers=4, d_model=2048, q_heads=32,
               kv_heads=4, head_dim=128, rope_theta=1e6, block_length=4,
               expert_dim=768, num_experts=128, experts_held=None, top_k=8,
               seq_len=8192, dtype="float32", **kwargs):
    """``seq_len`` is L, the sequence's own length: the trunk runs
    ``2 * seq_len`` rows.  ``block_length`` divides it."""
    E, F, L = int(num_experts), int(expert_dim), int(seq_len)
    Bk, V = int(block_length), int(num_classes)
    if Bk <= 0 or L % Bk:
        raise ValueError("block_length=%d does not divide seq_len=%d"
                         % (Bk, L))
    eps = 1e-6
    # norms mirrored: at 16 384 rows a norm's float32 intermediates
    # are 0.4 GB that the backward pass can make again from the stream
    frame = Decoder(V, d_model, E, experts_held, dtype, eps=eps,
                    force_mirroring=True)
    norm = frame.norm

    data = sym.Variable("data")                 # (B, 3, L)
    part = lambda i, name: sym.Reshape(
        sym.slice_axis(data, axis=1, begin=i, end=i + 1), shape=(0, -1),
        name=name)
    # [x0; xt] is the array's own first two rows, read in order
    both = sym.Reshape(sym.slice_axis(data, axis=1, begin=0, end=2),
                       shape=(0, -1), name="ids_clean_noised")
    # The residual stream stays float32 whatever the trunk's dtype.  Half
    # of the noised rows carry ONE embedding (MASK's) and differ by what
    # attention adds (a few percent of it): rounded to bfloat16 a layer,
    # rows that lie within a rounding of the same boundary between the
    # 8th and the 9th expert change their choice TOGETHER, and a
    # router's gradient follows them.  The sublayers read the stream in
    # the trunk's dtype; each router reads the normalised float32 rows.
    low = lambda s, name: sym.Cast(data=s, dtype=dtype, name=name) \
        if frame.low else s
    full = lambda s, name: sym.Cast(data=s, dtype="float32", name=name) \
        if frame.low else s
    x = frame.embed(ids=both, cast=False)
    counts = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        attn = sym.contrib.GroupedQueryAttention(
            low(norm(x, pre + "in_norm"), pre + "in_norm_low"),
            weight(pre + "attn_q_weight"),
            weight(pre + "attn_k_weight"), weight(pre + "attn_v_weight"),
            weight(pre + "attn_o_weight"),
            weight(pre + "attn_q_norm_gamma", _init.One()),
            weight(pre + "attn_k_norm_gamma", _init.One()),
            q_heads=int(q_heads), kv_heads=int(kv_heads),
            head_dim=int(head_dim), rope_theta=float(rope_theta),
            qk_norm=True, eps=eps, blocks=Bk, name=pre + "attn")
        x = x + full(attn, pre + "attn_full")

        rows32 = norm(x, pre + "post_norm")
        moe = sym.contrib.RoutedExperts(
            low(rows32, pre + "post_norm_low"),
            # 3-D stacks (held, out, in): Xavier would misread their fans
            gate_weight=weight(pre + "moe_gate_weight"),
            up_weight=weight(pre + "moe_up_weight"),
            down_weight=weight(pre + "moe_down_weight"),
            router_weight=weight(pre + "moe_router_weight", **F32),
            router="linear", top_k=int(top_k), num_experts=E,
            held_first=frame.first, held_count=frame.held, num_hidden=F,
            router_data=rows32, router_stream=True,
            # the masked rows choose alike: a layer's pairs here move by
            # a quarter of the even share with each favourite held
            rows_slack=2.0, name=pre + "moe")
        x = x + full(moe[0], pre + "moe_full")
        counts.append(moe[2])

    head = {}

    def rows(logits):
        head["op"] = sym.contrib.DiffusionHead(
            logits, part(1, "ids_noised"), part(2, "row_weight"),
            mask_id=V - 1, name="diffusion_head")
        return head["op"][0]

    noised = low(sym.slice_axis(x, axis=1, begin=L, end=2 * L,
                                name="noised_half"), "noised_half_low")
    out, tokens = frame.close(noised, counts, rows=rows)
    masked = sym.BlockGrad(head["op"][1], name=ROWS_NODE)
    return sym.Group([out, tokens, masked])
