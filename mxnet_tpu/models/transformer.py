"""Decoder-only transformer language model (GPT-style, pre-LN).

New TPU-native capability: the reference (MXNet ~1.2) predates
transformers entirely (SURVEY.md §5.7 maps its sequence stack to
RNN/BucketingModule), so this is not a ported symbol — it is the
arithmetic-intensity-dense model family that demonstrates the framework
reaches MXU-bound MFU when the model is not HBM-bandwidth-bound the way
ResNet/BatchNorm is. Attention is the fused
``sym.contrib.CausalSelfAttention`` op (rematerialized backward, fp32
softmax statistics); sequence/context-parallel training of the same
architecture runs through ``parallel.ring_attention``.

Builds a Symbol ending in SoftmaxOutput, so it drops into ``Module.fit``
/ ``parallel.TrainStep`` exactly like the CNN zoo:
``data`` is (batch, seq_len) token ids and ``softmax_label`` is
(batch*seq_len,) next-token targets.
"""
from .. import initializer as _init
from .. import symbol as sym


def get_symbol(num_classes=16384, num_layers=12, d_model=2048, num_heads=16,
               ffn_dim=None, seq_len=1024, dtype="float32", dropout=0.0,
               moe_experts=0, moe_every=2, moe_aux_coeff=0.01,
               tensor_parallel=None, **kwargs):
    """``num_classes`` is the vocabulary size (factory-signature parity
    with the CNN zoo's get_symbol). With ``moe_experts`` > 0 every
    ``moe_every``-th layer's FFN becomes a Switch-MoE
    (sym.contrib.SwitchMoE, num_experts experts, top-1 routing) and the
    load-balancing aux losses join the heads through MakeLoss scaled by
    ``moe_aux_coeff`` — a sparse-expert LM end-to-end in the symbolic
    API.

    ``tensor_parallel`` (docs/SHARDING.md): a mesh-axis name (True means
    "mp") that Megatron-splits every dense layer — attention heads and
    the packed qkv projection partition over the axis (column-parallel),
    the output/ffn_down projections are row-parallel with the psum at
    their replicated outputs, so each transformer block costs exactly
    two all-reduces in forward.  The annotations are plain
    ``__sharding__`` attrs: without a selected mesh the symbol trains
    replicated, unchanged."""
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    lp = float(dropout)
    aux_losses = []

    tp = "mp" if tensor_parallel is True else tensor_parallel
    if tp:
        from .. import sharding as _sharding
        if int(num_heads) < 2:
            raise ValueError("tensor_parallel needs num_heads >= 2")
        _col_w = {_sharding.SHARDING_ATTR: _sharding.spec(tp, None)}
        _col_b = {_sharding.SHARDING_ATTR: _sharding.spec(tp)}
        _row_w = {_sharding.SHARDING_ATTR: _sharding.spec(None, tp)}
        _replicate = lambda s: _sharding.constrain(s)
        _keep_split = lambda s: _sharding.constrain(s, None, None, tp)
    else:
        _col_w = _col_b = _row_w = {}
        _replicate = _keep_split = lambda s: s

    data = sym.Variable("data")                      # (B, S) token ids
    tok = sym.Embedding(data, input_dim=vocab, output_dim=d,
                        name="tok_embed")
    pos = sym.Variable("pos_embed_weight", shape=(1, int(seq_len), d))
    x = sym.broadcast_add(tok, pos, name="embed_add")
    if dtype in ("float16", "bfloat16"):
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")
    if lp > 0:
        x = sym.Dropout(data=x, p=lp, name="embed_drop")

    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        ln1 = sym.LayerNorm(data=x, name=pre + "ln1")
        # one fused sublayer op: qkv proj + causal MHA + out proj with
        # head-major internal layout (no transposes); weight names keep
        # the unfused FullyConnected convention so checkpoints interop
        attn_kw = {"head_axis": tp} if tp else {}
        proj = sym.contrib.FusedCausalSelfAttention(
            ln1,
            sym.Variable(pre + "qkv_weight", **_col_w),
            sym.Variable(pre + "qkv_bias", init=_init.Zero(), **_col_b),
            sym.Variable(pre + "proj_weight", **_row_w),
            sym.Variable(pre + "proj_bias", init=_init.Zero()),
            num_heads=int(num_heads), name=pre + "attn", **attn_kw)
        if tp:
            proj = _replicate(proj)   # the block's first psum site
        if lp > 0:
            proj = sym.Dropout(data=proj, p=lp, name=pre + "drop1")
        x = x + proj
        ln2 = sym.LayerNorm(data=x, name=pre + "ln2")
        if moe_experts and (i + 1) % max(int(moe_every), 1) == 0:
            # explicit expert-stack variables with per-expert-fan Normal
            # inits (Xavier misreads 3-D stacks: it would treat the
            # trailing dims as conv extents and under-scale ~sqrt(ffn)x)
            w_up = sym.Variable(pre + "moe_expert_up_weight",
                                init=_init.Normal(d ** -0.5))
            w_down = sym.Variable(pre + "moe_expert_down_weight",
                                  init=_init.Normal(ffn ** -0.5))
            moe = sym.contrib.SwitchMoE(
                ln2, expert_up_weight=w_up, expert_down_weight=w_down,
                num_experts=int(moe_experts), num_hidden=ffn,
                k=1, name=pre + "moe")
            h = moe[0]
            aux_losses.append(moe[1])
        else:
            # Megatron FFN: column-parallel up (weight (ffn, d) split on
            # its output rows), gelu on the still-split activation,
            # row-parallel down with the psum at its replicated output
            h = sym.FullyConnected(
                data=ln2,
                weight=sym.Variable(pre + "ffn_up_weight", **_col_w),
                bias=sym.Variable(pre + "ffn_up_bias", init=_init.Zero(),
                                  **_col_b),
                num_hidden=ffn, flatten=False, name=pre + "ffn_up")
            h = _keep_split(h)
            h = sym.LeakyReLU(data=h, act_type="gelu_tanh",
                              name=pre + "gelu")
            h = sym.FullyConnected(
                data=h,
                weight=sym.Variable(pre + "ffn_down_weight", **_row_w),
                bias=sym.Variable(pre + "ffn_down_bias",
                                  init=_init.Zero()),
                num_hidden=d, flatten=False, name=pre + "ffn_down")
            h = _replicate(h)
        if lp > 0:
            h = sym.Dropout(data=h, p=lp, name=pre + "drop2")
        x = x + h

    x = sym.LayerNorm(data=x, name="ln_f")
    logits = sym.FullyConnected(data=x, num_hidden=vocab, flatten=False,
                                name="lm_head")
    if dtype in ("float16", "bfloat16"):
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    flat = sym.Reshape(data=logits, shape=(-1, vocab), name="logits_2d")
    out = sym.SoftmaxOutput(data=flat, name="softmax",
                            normalization="batch")
    if aux_losses:
        total_aux = aux_losses[0] if len(aux_losses) == 1 else \
            sym.add_n(*aux_losses, name="moe_aux_sum")
        aux_head = sym.MakeLoss(
            sym.Cast(total_aux, dtype="float32", name="cast_aux")
            * float(moe_aux_coeff), name="moe_aux_loss")
        return sym.Group([out, aux_head])
    return out


# ----------------------------------------------------------------------
# Generative serving graphs (mx.decode — docs/DECODE.md)
#
# Both symbols below SHARE every weight name with get_symbol(), so the
# training checkpoint binds them with no conversion; they differ only
# in how attention addresses the paged KV cache
# (sym.contrib.PagedDecodeAttention / PagedPrefillAttention).  Cache
# variables carry explicit shapes (they are engine configuration, not
# inferable from data), and all sequence state — positions, lengths,
# block tables — enters as runtime ARRAY inputs so ragged generation
# never retraces the compiled step.
# ----------------------------------------------------------------------
def _tp_attrs(tensor_parallel):
    """Megatron ``__sharding__`` attr dicts for the decode-graph
    factories (mirrors get_symbol's training-side split): returns
    ``(col_w, col_b, row_w, cache)`` — empty dicts when tensor
    parallelism is off, so annotation-free symbols stay byte-identical.
    ``cache`` head-shards the paged KV blocks (num_blocks, block_size,
    H, D) over the axis, which is where the per-device cache-bytes
    saving of TP decode (docs/FLEET.md) comes from."""
    tp = "mp" if tensor_parallel is True else tensor_parallel
    if not tp:
        return {}, {}, {}, {}
    from .. import sharding as _sharding
    return ({_sharding.SHARDING_ATTR: _sharding.spec(tp, None)},
            {_sharding.SHARDING_ATTR: _sharding.spec(tp)},
            {_sharding.SHARDING_ATTR: _sharding.spec(None, tp)},
            {_sharding.SHARDING_ATTR: _sharding.spec(None, None, tp, None)})


def _decode_trunk_vars(pre, col_w={}, col_b={}, row_w={}):
    """The attention sublayer's weight variables, training-graph names."""
    return (sym.Variable(pre + "qkv_weight", **col_w),
            sym.Variable(pre + "qkv_bias", init=_init.Zero(), **col_b),
            sym.Variable(pre + "proj_weight", **row_w),
            sym.Variable(pre + "proj_bias", init=_init.Zero()))


def _ffn_shared_vars(pre, d, ffn, moe_experts, moe_every, layer_idx,
                     col_w={}, col_b={}, row_w={}):
    """Explicit post-attention sublayer weight Variables (training-graph
    names) so the mixed-step symbol's two streams — decode slots and the
    prefill chunk — bind ONE copy of every parameter."""
    use_moe = moe_experts and (layer_idx + 1) % max(int(moe_every), 1) == 0
    shared = {
        "ln2_gamma": sym.Variable(pre + "ln2_gamma"),
        "ln2_beta": sym.Variable(pre + "ln2_beta", init=_init.Zero()),
    }
    if use_moe:
        shared.update({
            "router_weight": sym.Variable(pre + "moe_router_weight"),
            "expert_up_weight": sym.Variable(
                pre + "moe_expert_up_weight", init=_init.Normal(d ** -0.5)),
            "expert_up_bias": sym.Variable(pre + "moe_expert_up_bias",
                                           init=_init.Zero()),
            "expert_down_weight": sym.Variable(
                pre + "moe_expert_down_weight",
                init=_init.Normal(ffn ** -0.5)),
            "expert_down_bias": sym.Variable(pre + "moe_expert_down_bias",
                                             init=_init.Zero()),
        })
    else:
        shared.update({
            "up_weight": sym.Variable(pre + "ffn_up_weight", **col_w),
            "up_bias": sym.Variable(pre + "ffn_up_bias",
                                    init=_init.Zero(), **col_b),
            "down_weight": sym.Variable(pre + "ffn_down_weight", **row_w),
            "down_bias": sym.Variable(pre + "ffn_down_bias",
                                      init=_init.Zero()),
        })
    return shared


def _decode_ffn(x, pre, d, ffn, moe_experts, moe_every, layer_idx,
                shared=None, tag=""):
    """Post-attention FFN sublayer shared by the decode/prefill graphs
    (inference form: MoE aux losses are dropped, dropout is off).

    ``shared`` (a `_ffn_shared_vars` dict) passes every weight as an
    explicit Variable — the mixed-step symbol instantiates this sublayer
    twice against ONE parameter set; ``tag`` keeps the second instance's
    op names distinct (variable names are unchanged either way)."""
    use_moe = moe_experts and (layer_idx + 1) % max(int(moe_every), 1) == 0
    ln_kw = ({"gamma": shared["ln2_gamma"], "beta": shared["ln2_beta"]}
             if shared else {})
    ln2 = sym.LayerNorm(data=x, name=pre + tag + "ln2", **ln_kw)
    if use_moe:
        if shared:
            w_up, w_down = (shared["expert_up_weight"],
                            shared["expert_down_weight"])
            moe_kw = {"router_weight": shared["router_weight"],
                      "expert_up_bias": shared["expert_up_bias"],
                      "expert_down_bias": shared["expert_down_bias"]}
        else:
            w_up = sym.Variable(pre + "moe_expert_up_weight",
                                init=_init.Normal(d ** -0.5))
            w_down = sym.Variable(pre + "moe_expert_down_weight",
                                  init=_init.Normal(ffn ** -0.5))
            moe_kw = {}
        moe = sym.contrib.SwitchMoE(
            ln2, expert_up_weight=w_up, expert_down_weight=w_down,
            num_experts=int(moe_experts), num_hidden=ffn,
            k=1, name=pre + tag + "moe", **moe_kw)
        return moe[0]
    up_kw = ({"weight": shared["up_weight"], "bias": shared["up_bias"]}
             if shared else {})
    down_kw = ({"weight": shared["down_weight"],
                "bias": shared["down_bias"]} if shared else {})
    h = sym.FullyConnected(data=ln2, num_hidden=ffn, flatten=False,
                           name=pre + tag + "ffn_up", **up_kw)
    h = sym.LeakyReLU(data=h, act_type="gelu_tanh", name=pre + tag + "gelu")
    return sym.FullyConnected(data=h, num_hidden=d, flatten=False,
                              name=pre + tag + "ffn_down", **down_kw)


def get_decode_step_symbol(num_classes=16384, num_layers=12, d_model=2048,
                           num_heads=16, ffn_dim=None, seq_len=1024,
                           dtype="float32", block_size=16, num_blocks=64,
                           moe_experts=0, moe_every=2, **kwargs):
    """One cached autoregressive decode step over C fixed batch slots.

    Inputs (bound shapes set capacity C and table width M):
      ``data`` (C, 1) current token ids; ``positions`` (C, 1) 0-based
      position of that token (< 0 = inactive slot); ``block_table``
      (C, M) per-slot cache block ids; plus per-layer
      ``layer%d_k_cache`` / ``layer%d_v_cache`` paged caches of shape
      (num_blocks, block_size, H, D) that the engine threads from step
      to step.
    Outputs: ``[logits (C, vocab), greedy next token (C,),
    new_k_cache_0, new_v_cache_0, ...]`` — the greedy token ships as
    its own output so a default decode step reads back C ints, not a
    (C, vocab) logits matrix; samplers read output 0 instead.
    """
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    H = int(num_heads)
    D = d // H

    data = sym.Variable("data")                      # (C, 1) token ids
    positions = sym.Variable("positions")            # (C, 1)
    table = sym.Variable("block_table")              # (C, M)
    tok = sym.Embedding(data, input_dim=vocab, output_dim=d,
                        name="tok_embed")
    pos_w = sym.Variable("pos_embed_weight", shape=(1, int(seq_len), d))
    pe = sym.take(sym.Reshape(pos_w, shape=(int(seq_len), d)), positions,
                  name="pos_take")                   # (C, 1, d), clipped
    x = tok + pe
    if dtype in ("float16", "bfloat16"):
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")

    new_kv = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        ln1 = sym.LayerNorm(data=x, name=pre + "ln1")
        kc = sym.Variable(pre + "k_cache",
                          shape=(int(num_blocks), int(block_size), H, D))
        vc = sym.Variable(pre + "v_cache",
                          shape=(int(num_blocks), int(block_size), H, D))
        att = sym.contrib.PagedDecodeAttention(
            ln1, *_decode_trunk_vars(pre), kc, vc, table, positions,
            num_heads=H, name=pre + "attn")
        x = x + att[0]
        new_kv += [att[1], att[2]]
        x = x + _decode_ffn(x, pre, d, ffn, moe_experts, moe_every, i)

    x = sym.LayerNorm(data=x, name="ln_f")
    logits = sym.FullyConnected(data=x, num_hidden=vocab, flatten=False,
                                name="lm_head")      # (C, 1, vocab)
    if dtype in ("float16", "bfloat16"):
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    flat = sym.Reshape(data=logits, shape=(-1, vocab), name="logits_2d")
    nxt = sym.argmax(flat, axis=1, name="greedy_token")
    return sym.Group([flat, nxt] + new_kv)


def get_prefill_symbol(num_classes=16384, num_layers=12, d_model=2048,
                       num_heads=16, ffn_dim=None, seq_len=1024,
                       prefill_len=None, dtype="float32", block_size=16,
                       num_blocks=64, moe_experts=0, moe_every=2, **kwargs):
    """Prompt-phase forward that populates the paged KV cache.

    ``prefill_len`` is this bucket's padded prompt length S_b (the
    engine keeps a power-of-two ladder of these symbols, one compile
    each — the decode analog of serving's batch-size buckets).  Inputs:
    ``data`` (B, S_b) padded prompt ids, ``prompt_len`` (B,) real
    lengths, ``block_table`` (B, M), plus the same per-layer cache
    variables as the decode step.  Outputs: ``[last-token logits
    (B, vocab), greedy next token (B,), new caches...]``.
    """
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    H = int(num_heads)
    D = d // H
    S = int(prefill_len) if prefill_len else int(seq_len)
    if S > int(seq_len):
        raise ValueError("prefill_len %d exceeds the position-embedding "
                         "range seq_len=%d" % (S, int(seq_len)))

    data = sym.Variable("data")                      # (B, S) token ids
    lengths = sym.Variable("prompt_len")             # (B,)
    table = sym.Variable("block_table")              # (B, M)
    tok = sym.Embedding(data, input_dim=vocab, output_dim=d,
                        name="tok_embed")
    pos_w = sym.Variable("pos_embed_weight", shape=(1, int(seq_len), d))
    pe = pos_w.slice_axis(axis=1, begin=0, end=S)
    x = sym.broadcast_add(tok, pe, name="embed_add")
    if dtype in ("float16", "bfloat16"):
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")

    new_kv = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        ln1 = sym.LayerNorm(data=x, name=pre + "ln1")
        kc = sym.Variable(pre + "k_cache",
                          shape=(int(num_blocks), int(block_size), H, D))
        vc = sym.Variable(pre + "v_cache",
                          shape=(int(num_blocks), int(block_size), H, D))
        att = sym.contrib.PagedPrefillAttention(
            ln1, *_decode_trunk_vars(pre), kc, vc, table, lengths,
            num_heads=H, name=pre + "attn")
        x = x + att[0]
        new_kv += [att[1], att[2]]
        x = x + _decode_ffn(x, pre, d, ffn, moe_experts, moe_every, i)

    x = sym.LayerNorm(data=x, name="ln_f")
    last = sym.contrib.GatherTimestep(x, lengths - 1, name="last_token")
    logits = sym.FullyConnected(data=last, num_hidden=vocab, flatten=False,
                                name="lm_head")      # (B, vocab)
    if dtype in ("float16", "bfloat16"):
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    nxt = sym.argmax(logits, axis=1, name="greedy_token")
    return sym.Group([logits, nxt] + new_kv)


def get_mixed_step_symbol(num_classes=16384, num_layers=12, d_model=2048,
                          num_heads=16, ffn_dim=None, seq_len=1024,
                          dtype="float32", block_size=16, num_blocks=64,
                          moe_experts=0, moe_every=2, tensor_parallel=None,
                          **kwargs):
    """ONE decode iteration with chunked prefill fused in (stall-free
    scheduling, docs/DECODE.md): up to K prefill-chunk tokens of one
    admitted prompt AND one decode token for every active slot run in
    the same compiled, donated launch.

    Two streams share every parameter (each weight is created once as
    an explicit Variable and bound by both op instances, so the graph
    has ONE copy and checkpoints load unchanged):

    * decode stream — identical to `get_decode_step_symbol`: ``data``
      (C, 1), ``positions`` (C, 1) (< 0 = inactive), ``block_table``
      (C, M), PagedDecodeAttention per layer;
    * chunk stream — ``chunk_data`` (1, K) the current prompt chunk,
      ``chunk_positions`` (1, K) its absolute positions (for the
      position embedding), ``chunk_start`` (1,) / ``chunk_len`` (1,)
      the chunk's absolute offset and real token count
      (``chunk_len == 0`` disables the stream for the iteration), and
      ``chunk_table`` (1, M) the prefilling sequence's blocks;
      PagedChunkPrefillAttention attends the chunk causally against the
      cache prefix written by earlier chunks.

    Cache variables thread decode-write -> chunk-write per layer, so
    one donated buffer chain carries both streams.  K and C are set at
    bind time by the input shapes — the symbol itself is geometry-free.
    Outputs: ``[decode logits (C, vocab), decode greedy token (C,),
    chunk last-token logits (1, vocab), chunk greedy token (1,),
    new caches...]`` — the chunk head's greedy token is the sequence's
    FIRST generated token once its final chunk lands.

    ``tensor_parallel`` (docs/FLEET.md): a mesh-axis name (True means
    "mp") Megatron-splitting every dense layer exactly as in
    get_symbol(), PLUS head-sharding the paged KV caches over the axis
    — each device holds 1/mp of every cache block, so TP decode scales
    cache capacity with the mesh.  Annotations only; without a selected
    mesh the symbol binds replicated, unchanged.
    """
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    H = int(num_heads)
    D = d // H
    _col_w, _col_b, _row_w, _cache = _tp_attrs(tensor_parallel)

    data = sym.Variable("data")                      # (C, 1) token ids
    positions = sym.Variable("positions")            # (C, 1)
    table = sym.Variable("block_table")              # (C, M)
    cdata = sym.Variable("chunk_data")               # (1, K) chunk ids
    cpos = sym.Variable("chunk_positions")           # (1, K) absolute
    cstart = sym.Variable("chunk_start")             # (1,)
    clen = sym.Variable("chunk_len")                 # (1,)
    ctable = sym.Variable("chunk_table")             # (1, M)

    tokw = sym.Variable("tok_embed_weight")
    pos_w = sym.Variable("pos_embed_weight", shape=(1, int(seq_len), d))
    pos_flat = sym.Reshape(pos_w, shape=(int(seq_len), d))

    tok = sym.Embedding(data, tokw, input_dim=vocab, output_dim=d,
                        name="tok_embed")
    x = tok + sym.take(pos_flat, positions, name="pos_take")
    ctok = sym.Embedding(cdata, tokw, input_dim=vocab, output_dim=d,
                         name="c_tok_embed")
    xc = ctok + sym.take(pos_flat, cpos, name="c_pos_take")
    if dtype in ("float16", "bfloat16"):
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")
        xc = sym.Cast(data=xc, dtype=dtype, name="c_cast_embed")

    new_kv = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        attn_vars = _decode_trunk_vars(pre, _col_w, _col_b, _row_w)
        ln1_g = sym.Variable(pre + "ln1_gamma")
        ln1_b = sym.Variable(pre + "ln1_beta", init=_init.Zero())
        kc = sym.Variable(pre + "k_cache",
                          shape=(int(num_blocks), int(block_size), H, D),
                          **_cache)
        vc = sym.Variable(pre + "v_cache",
                          shape=(int(num_blocks), int(block_size), H, D),
                          **_cache)

        ln1 = sym.LayerNorm(data=x, gamma=ln1_g, beta=ln1_b,
                            name=pre + "ln1")
        att = sym.contrib.PagedDecodeAttention(
            ln1, *attn_vars, kc, vc, table, positions,
            num_heads=H, name=pre + "attn")
        x = x + att[0]

        # the chunk reads/writes the cache AFTER the decode scatter —
        # one coherent donated buffer chain; block tables are disjoint
        # (a sequence is either prefilling or decoding, never both in
        # one launch), so the streams never alias a block
        cln1 = sym.LayerNorm(data=xc, gamma=ln1_g, beta=ln1_b,
                             name=pre + "c_ln1")
        catt = sym.contrib.PagedChunkPrefillAttention(
            cln1, *attn_vars, att[1], att[2], ctable, cstart, clen,
            num_heads=H, name=pre + "c_attn")
        xc = xc + catt[0]
        new_kv += [catt[1], catt[2]]

        shared = _ffn_shared_vars(pre, d, ffn, moe_experts, moe_every, i,
                                  _col_w, _col_b, _row_w)
        x = x + _decode_ffn(x, pre, d, ffn, moe_experts, moe_every, i,
                            shared=shared)
        xc = xc + _decode_ffn(xc, pre, d, ffn, moe_experts, moe_every, i,
                              shared=shared, tag="c_")

    lnf_g = sym.Variable("ln_f_gamma")
    lnf_b = sym.Variable("ln_f_beta", init=_init.Zero())
    lmw = sym.Variable("lm_head_weight")
    lmb = sym.Variable("lm_head_bias", init=_init.Zero())

    x = sym.LayerNorm(data=x, gamma=lnf_g, beta=lnf_b, name="ln_f")
    logits = sym.FullyConnected(data=x, weight=lmw, bias=lmb,
                                num_hidden=vocab, flatten=False,
                                name="lm_head")      # (C, 1, vocab)
    if dtype in ("float16", "bfloat16"):
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    flat = sym.Reshape(data=logits, shape=(-1, vocab), name="logits_2d")
    nxt = sym.argmax(flat, axis=1, name="greedy_token")

    xc = sym.LayerNorm(data=xc, gamma=lnf_g, beta=lnf_b, name="c_ln_f")
    clast = sym.contrib.GatherTimestep(xc, clen - 1, name="c_last_token")
    clogits = sym.FullyConnected(data=clast, weight=lmw, bias=lmb,
                                 num_hidden=vocab, flatten=False,
                                 name="c_lm_head")   # (1, vocab)
    if dtype in ("float16", "bfloat16"):
        clogits = sym.Cast(data=clogits, dtype="float32",
                           name="c_cast_out")
    cnxt = sym.argmax(clogits, axis=1, name="c_greedy_token")
    return sym.Group([flat, nxt, clogits, cnxt] + new_kv)


def get_spec_step_symbol(num_classes=16384, num_layers=12, d_model=2048,
                         num_heads=16, ffn_dim=None, seq_len=1024,
                         dtype="float32", block_size=16, num_blocks=64,
                         moe_experts=0, moe_every=2, tensor_parallel=None,
                         **kwargs):
    """The mixed step generalized to draft-verify spans (speculative
    decoding, docs/DECODE.md): instead of ONE token per slot, every
    iteration scores an S-token span per slot — the slot's last
    committed token followed by up to S-1 draft tokens — so the engine
    can accept several tokens from a single compiled, donated launch.

    The decode stream of `get_mixed_step_symbol` is replaced by a SPAN
    stream built on the same chunk-attention primitive the prompt
    chunk uses (``PagedChunkPrefillAttention`` is B-row capable:
    per-row start/length, zero-length rows are no-ops), batched across
    all C slots:

    * span stream — ``data`` (C, S) span token ids (row r holds the
      slot's last token then its draft; tail padded), ``positions``
      (C, S) absolute positions (pad rows 0 — harmless, masked by
      length), ``span_start`` (C,) each row's absolute cache offset,
      ``span_len`` (C,) real span tokens (0 = inactive slot),
      ``block_table`` (C, M); per layer the span scatters its K/V at
      positions ``span_start + j`` and attends causally against the
      slot's whole cache prefix — exactly verification: row j's logits
      condition on every committed token plus draft tokens < j;
    * chunk stream — unchanged from the mixed step (chunked prefill
      continues to ride along), reading the cache AFTER the span
      scatter in the same donated buffer chain.

    With S == 1 the span stream degenerates to exactly one token per
    slot — plain decoding through the chunk-attention primitive.
    Rejected draft rows leave K/V entries above the accepted prefix;
    they are dead by construction: the next iteration's span starts at
    the first rejected position and its scatter overwrites those rows
    before any query can attend them (scatter-then-gather inside the
    op, causal mask ``j <= pos``).

    Outputs: ``[span logits (C*S, vocab), span greedy tokens (C*S,),
    chunk last-token logits (1, vocab), chunk greedy token (1,),
    new caches...]`` — same base layout as the mixed step, so the
    engine's cache-commit and chunk-completion paths are shared.  Row
    ``r*S + j`` is slot r, span offset j; greedy token at offset j is
    the target model's choice for position ``span_start + j + 1``.

    ``tensor_parallel``: same Megatron split + head-sharded caches as
    get_mixed_step_symbol (docs/FLEET.md).
    """
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    H = int(num_heads)
    D = d // H
    _col_w, _col_b, _row_w, _cache = _tp_attrs(tensor_parallel)

    data = sym.Variable("data")                      # (C, S) span ids
    positions = sym.Variable("positions")            # (C, S) absolute
    sstart = sym.Variable("span_start")              # (C,)
    slen = sym.Variable("span_len")                  # (C,) 0 = inactive
    table = sym.Variable("block_table")              # (C, M)
    cdata = sym.Variable("chunk_data")               # (1, K) chunk ids
    cpos = sym.Variable("chunk_positions")           # (1, K) absolute
    cstart = sym.Variable("chunk_start")             # (1,)
    clen = sym.Variable("chunk_len")                 # (1,)
    ctable = sym.Variable("chunk_table")             # (1, M)

    tokw = sym.Variable("tok_embed_weight")
    pos_w = sym.Variable("pos_embed_weight", shape=(1, int(seq_len), d))
    pos_flat = sym.Reshape(pos_w, shape=(int(seq_len), d))

    tok = sym.Embedding(data, tokw, input_dim=vocab, output_dim=d,
                        name="tok_embed")
    x = tok + sym.take(pos_flat, positions, name="pos_take")
    ctok = sym.Embedding(cdata, tokw, input_dim=vocab, output_dim=d,
                         name="c_tok_embed")
    xc = ctok + sym.take(pos_flat, cpos, name="c_pos_take")
    if dtype in ("float16", "bfloat16"):
        x = sym.Cast(data=x, dtype=dtype, name="cast_embed")
        xc = sym.Cast(data=xc, dtype=dtype, name="c_cast_embed")

    new_kv = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        attn_vars = _decode_trunk_vars(pre, _col_w, _col_b, _row_w)
        ln1_g = sym.Variable(pre + "ln1_gamma")
        ln1_b = sym.Variable(pre + "ln1_beta", init=_init.Zero())
        kc = sym.Variable(pre + "k_cache",
                          shape=(int(num_blocks), int(block_size), H, D),
                          **_cache)
        vc = sym.Variable(pre + "v_cache",
                          shape=(int(num_blocks), int(block_size), H, D),
                          **_cache)

        ln1 = sym.LayerNorm(data=x, gamma=ln1_g, beta=ln1_b,
                            name=pre + "ln1")
        att = sym.contrib.PagedChunkPrefillAttention(
            ln1, *attn_vars, kc, vc, table, sstart, slen,
            num_heads=H, name=pre + "attn")
        x = x + att[0]

        # chunk reads/writes the cache AFTER the span scatter — one
        # coherent donated chain; a sequence is either prefilling or
        # decoding, never both in one launch, so the streams never
        # alias a block (prefix-shared blocks are read-only in both)
        cln1 = sym.LayerNorm(data=xc, gamma=ln1_g, beta=ln1_b,
                             name=pre + "c_ln1")
        catt = sym.contrib.PagedChunkPrefillAttention(
            cln1, *attn_vars, att[1], att[2], ctable, cstart, clen,
            num_heads=H, name=pre + "c_attn")
        xc = xc + catt[0]
        new_kv += [catt[1], catt[2]]

        shared = _ffn_shared_vars(pre, d, ffn, moe_experts, moe_every, i,
                                  _col_w, _col_b, _row_w)
        x = x + _decode_ffn(x, pre, d, ffn, moe_experts, moe_every, i,
                            shared=shared)
        xc = xc + _decode_ffn(xc, pre, d, ffn, moe_experts, moe_every, i,
                              shared=shared, tag="c_")

    lnf_g = sym.Variable("ln_f_gamma")
    lnf_b = sym.Variable("ln_f_beta", init=_init.Zero())
    lmw = sym.Variable("lm_head_weight")
    lmb = sym.Variable("lm_head_bias", init=_init.Zero())

    x = sym.LayerNorm(data=x, gamma=lnf_g, beta=lnf_b, name="ln_f")
    logits = sym.FullyConnected(data=x, weight=lmw, bias=lmb,
                                num_hidden=vocab, flatten=False,
                                name="lm_head")      # (C, S, vocab)
    if dtype in ("float16", "bfloat16"):
        logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
    flat = sym.Reshape(data=logits, shape=(-1, vocab), name="logits_2d")
    nxt = sym.argmax(flat, axis=1, name="greedy_token")

    xc = sym.LayerNorm(data=xc, gamma=lnf_g, beta=lnf_b, name="c_ln_f")
    clast = sym.contrib.GatherTimestep(xc, clen - 1, name="c_last_token")
    clogits = sym.FullyConnected(data=clast, weight=lmw, bias=lmb,
                                 num_hidden=vocab, flatten=False,
                                 name="c_lm_head")   # (1, vocab)
    if dtype in ("float16", "bfloat16"):
        clogits = sym.Cast(data=clogits, dtype="float32",
                           name="c_cast_out")
    cnxt = sym.argmax(clogits, axis=1, name="c_greedy_token")
    return sym.Group([flat, nxt, clogits, cnxt] + new_kv)


def get_draft_span_symbol(draft_k, num_classes=16384, num_layers=12,
                          d_model=2048, num_heads=16, ffn_dim=None,
                          seq_len=1024, dtype="float32", moe_experts=0,
                          moe_every=2, **kwargs):
    """ONE compiled program proposing ``draft_k`` greedy draft tokens
    (mx.speculative, docs/DECODE.md): the autoregressive draft loop of
    ``DraftModelDrafter`` — K full forwards, K argmax readbacks —
    unrolled into a single graph, so a proposal costs exactly one
    dispatch and one K-int readback whatever K is.

    Every unrolled iteration shares ONE copy of every weight (explicit
    Variables bound by all K trunk instances under per-iteration op-name
    tags — the mixed-step symbol's sharing pattern), and the draft
    checkpoint loads unchanged.  Inputs: ``data`` (1, seq_len) the
    left-aligned, zero-padded token history; ``length`` (1,) its real
    token count n; ``iota`` (1, seq_len) a runtime ``arange(seq_len)``
    (fed once — symbols have no shape-dependent constants).  Iteration
    j reads the hidden row at position ``n + j - 1``, takes the greedy
    token, and writes it back into ``data`` at position ``n + j`` via
    an iota-mask blend — token j+1 conditions on token j entirely
    on-device.  Output: the (draft_k,) proposed token ids — the ONE
    readback.  Rows past ``seq_len`` never arise: the drafter trims its
    context to ``seq_len - draft_k`` tokens before feeding.

    Causal masking keeps the padded tail invisible to every row that is
    read, so the proposals equal the sequential drafter's exactly.
    """
    vocab = int(num_classes)
    d = int(d_model)
    ffn = int(ffn_dim) if ffn_dim else 4 * d
    H = int(num_heads)
    S = int(seq_len)
    K = int(draft_k)
    if K < 1:
        raise ValueError("get_draft_span_symbol: draft_k must be >= 1")

    data = sym.Variable("data")                      # (1, S) history ids
    length = sym.Variable("length")                  # (1,) real count
    iota = sym.Variable("iota")                      # (1, S) arange(S)

    tokw = sym.Variable("tok_embed_weight")
    pos_w = sym.Variable("pos_embed_weight", shape=(1, S, d))
    lnf_g = sym.Variable("ln_f_gamma")
    lnf_b = sym.Variable("ln_f_beta", init=_init.Zero())
    lmw = sym.Variable("lm_head_weight")
    lmb = sym.Variable("lm_head_bias", init=_init.Zero())
    layers = []
    for i in range(int(num_layers)):
        pre = "layer%d_" % i
        layers.append({
            "pre": pre,
            "ln1_g": sym.Variable(pre + "ln1_gamma"),
            "ln1_b": sym.Variable(pre + "ln1_beta", init=_init.Zero()),
            "attn": _decode_trunk_vars(pre),
            "ffn": _ffn_shared_vars(pre, d, ffn, moe_experts, moe_every,
                                    i),
        })

    toks = []
    for j in range(K):
        tag = "d%d_" % j
        tok = sym.Embedding(data, tokw, input_dim=vocab, output_dim=d,
                            name=tag + "tok_embed")
        x = sym.broadcast_add(tok, pos_w, name=tag + "embed_add")
        if dtype in ("float16", "bfloat16"):
            x = sym.Cast(data=x, dtype=dtype, name=tag + "cast_embed")
        for i, ly in enumerate(layers):
            pre = ly["pre"]
            ln1 = sym.LayerNorm(data=x, gamma=ly["ln1_g"],
                                beta=ly["ln1_b"], name=pre + tag + "ln1")
            proj = sym.contrib.FusedCausalSelfAttention(
                ln1, *ly["attn"], num_heads=H, name=pre + tag + "attn")
            x = x + proj
            x = x + _decode_ffn(x, pre, d, ffn, moe_experts, moe_every,
                                i, shared=ly["ffn"], tag=tag)
        x = sym.LayerNorm(data=x, gamma=lnf_g, beta=lnf_b,
                          name=tag + "ln_f")
        # the greedy next token after n + j committed tokens lives in
        # row n + j - 1 (causal: it saw exactly the real history plus
        # drafts < j; the padded tail sits behind the mask)
        last = sym.contrib.GatherTimestep(x, length + (float(j) - 1.0),
                                          name=tag + "last_token")
        logits = sym.FullyConnected(data=last, weight=lmw, bias=lmb,
                                    num_hidden=vocab,
                                    name=tag + "lm_head")  # (1, vocab)
        if dtype in ("float16", "bfloat16"):
            logits = sym.Cast(data=logits, dtype="float32",
                              name=tag + "cast_out")
        nxt = sym.argmax(logits, axis=1, name=tag + "greedy")   # (1,)
        toks.append(nxt)
        if j + 1 < K:
            # scatter the token at position n + j: data += mask*(t - data)
            posj = sym.Reshape(length + float(j), shape=(1, 1),
                               name=tag + "pos2d")
            onehot = sym.broadcast_equal(iota, posj, name=tag + "onehot")
            tok2d = sym.Reshape(nxt, shape=(1, 1), name=tag + "tok2d")
            data = data + onehot * (tok2d - data)

    return sym.Concat(*toks, dim=0, name="draft_tokens")    # (K,)
