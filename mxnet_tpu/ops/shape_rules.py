"""Backward shape-inference rules for parameterized ops.

The reference implements full bidirectional shape inference per op
(FInferShape, e.g. src/operator/nn/fully_connected.cc:55-95) so that
``simple_bind`` can size weights from data shapes alone. On TPU the forward
direction is free (``jax.eval_shape``); only the backward direction —
"given data shape + attrs, what are the parameter shapes" — needs rules,
and only for ops that own parameters. Also declares which optional inputs
are absent for given attrs (nnvm's FListInputNames dependence on params).
"""
from __future__ import annotations

import numpy as _np

from .registry import get_op
from .rnn import rnn_param_size


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _set(opname, param_shapes=None, unused_inputs=None):
    op = get_op(opname)
    if param_shapes is not None:
        op.param_shapes = param_shapes
    if unused_inputs is not None:
        op.unused_inputs = unused_inputs


def _fc_shapes(known, attrs):
    out = {}
    data = known.get("data")
    nh = int(attrs["num_hidden"])
    if data is not None:
        in_dim = _prod(data[1:]) if attrs.get("flatten", True) else data[-1]
        out["weight"] = (nh, in_dim)
    out["bias"] = (nh,)
    return out


_set("FullyConnected", _fc_shapes,
     lambda attrs: {"bias"} if attrs.get("no_bias") else set())


def _conv_shapes(known, attrs):
    out = {}
    data = known.get("data")
    nf = int(attrs["num_filter"])
    kernel = tuple(int(k) for k in attrs["kernel"])
    g = int(attrs.get("num_group", 1))
    layout = attrs.get("layout")
    if data is not None:
        if layout and str(layout).endswith("C"):
            # channel-last data pairs with channel-last weights
            out["weight"] = (nf,) + kernel + (data[-1] // g,)
        else:
            out["weight"] = (nf, data[1] // g) + kernel
    out["bias"] = (nf,)
    return out


_set("Convolution", _conv_shapes,
     lambda attrs: {"bias"} if attrs.get("no_bias") else set())


def _deconv_shapes(known, attrs):
    out = {}
    data = known.get("data")
    nf = int(attrs["num_filter"])
    kernel = tuple(int(k) for k in attrs["kernel"])
    g = int(attrs.get("num_group", 1))
    if data is not None:
        out["weight"] = (data[1], nf // g) + kernel
    out["bias"] = (nf,)
    return out


_set("Deconvolution", _deconv_shapes,
     lambda attrs: {"bias"} if attrs.get("no_bias", True) else set())


def _channel_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    ax = int(attrs.get("axis", 1)) % len(data)
    c = (data[ax],)
    return {"gamma": c, "beta": c, "moving_mean": c, "moving_var": c}


_set("BatchNorm", _channel_shapes)
_set("_contrib_SyncBatchNorm", _channel_shapes)


def _switch_moe_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = data[-1]
    E = int(attrs["num_experts"])
    h = int(attrs["num_hidden"])
    return {"router_weight": (d, E),
            "expert_up_weight": (E, d, h), "expert_up_bias": (E, h),
            "expert_down_weight": (E, h, d), "expert_down_bias": (E, d)}


_set("_contrib_SwitchMoE", _switch_moe_shapes)


def _routed_experts_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    E, h, R = (int(attrs.get(k, 0)) for k in ("num_experts", "num_hidden",
                                              "router_hidden"))
    Fs = int(attrs.get("shared_hidden", 0))
    held = attrs.get("held_count")
    held = E - int(attrs.get("held_first", 0)) if held is None else int(held)
    return {"router_state": tuple(data[:-1]) + (R,),
            "router_in_weight": (R, d), "router_carry": (1,),
            "router_norm_gamma": (R,), "router_fc1_weight": (R, R),
            "router_fc2_weight": (R, R), "router_out_weight": (E, R),
            "gate_weight": (held, h, d), "up_weight": (held, h, d),
            "down_weight": (held, d, h), "router_weight": (E, d),
            "shared_gate_weight": (Fs, d), "shared_up_weight": (Fs, d),
            "shared_down_weight": (d, Fs), "shared_sg_weight": (1, d),
            "router_bias": (E,), "router_data": tuple(data)}


_ZAYA_ROUTER = {"router_in_weight", "router_norm_gamma", "router_fc1_weight",
                "router_fc2_weight", "router_out_weight", "router_state",
                "router_carry"}
_SHARED_EXPERT = {"shared_gate_weight", "shared_up_weight",
                  "shared_down_weight", "shared_sg_weight"}


def _routed_experts_unused(attrs):
    """The router's inputs follow ``router``, the shared expert's
    ``shared_hidden``; the zaya router's first layer takes no state."""
    out = set() if int(attrs.get("shared_hidden", 0)) else set(_SHARED_EXPERT)
    if not attrs.get("shared_gate", True):
        out.add("shared_sg_weight")
    router = attrs.get("router", "zaya")
    if not attrs.get("router_stream", False):
        out.add("router_data")
    if router != "sigmoid":
        out.add("router_bias")
    if router == "zaya":
        out.add("router_weight")
        if not attrs.get("carry_in", True):
            out |= {"router_state", "router_carry"}
    else:
        out |= _ZAYA_ROUTER
    return out


_set("_contrib_RoutedExperts", _routed_experts_shapes, _routed_experts_unused)


def _gated_attn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    Hq, Hk, D = (int(attrs[k]) for k in ("q_heads", "kv_heads", "head_dim"))
    return {"q_weight": (2 * Hq * D, d), "k_weight": (Hk * D, d),
            "v_weight": (Hk * D, d), "q_norm_gamma": (D,),
            "k_norm_gamma": (D,), "o_weight": (d, Hq * D)}


_set("_contrib_GatedCausalSelfAttention", _gated_attn_shapes)


def _gqa_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    Hq, Hk, D = (int(attrs[k]) for k in ("q_heads", "kv_heads", "head_dim"))
    return {"q_weight": (Hq * D, d), "k_weight": (Hk * D, d),
            "v_weight": (Hk * D, d), "o_weight": (d, Hq * D),
            "q_norm_gamma": (D,), "k_norm_gamma": (D,)}


_set("_contrib_GroupedQueryAttention", _gqa_shapes,
     # the two gains come with ``qk_norm``
     unused_inputs=lambda attrs: set() if attrs.get("qk_norm")
     else {"q_norm_gamma", "k_norm_gamma"})


def _latent_attn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    H, Dn, Dr, Dv, C = (int(attrs[k]) for k in (
        "heads", "nope_dim", "rope_dim", "v_dim", "kv_rank"))
    return {"q_weight": (H * (Dn + Dr), d), "kva_weight": (C + Dr, d),
            "kv_norm_gamma": (C,), "kvb_weight": (H * (Dn + Dv), C),
            "o_weight": (d, H * Dv)}


_set("_contrib_LatentAttention", _latent_attn_shapes)


def _sparse_indexed_attn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    Hq, Hk, D, Hi, Di = (int(attrs[k]) for k in (
        "q_heads", "kv_heads", "head_dim", "idx_heads", "idx_dim"))
    return {"q_weight": (Hq * D, d), "k_weight": (Hk * D, d),
            "v_weight": (Hk * D, d), "q_norm_gamma": (D,),
            "k_norm_gamma": (D,), "o_weight": (d, Hq * D),
            "idx_q_weight": (Hi * Di, d), "idx_k_weight": (Di, d),
            "idx_w_weight": (Hi, d), "idx_k_norm_gamma": (Di,),
            "idx_k_norm_beta": (Di,)}


_set("_contrib_SparseIndexedAttention", _sparse_indexed_attn_shapes)


def _gated_ffn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d, F = int(data[-1]), int(attrs["num_hidden"])
    return {"gate_weight": (F, d), "up_weight": (F, d),
            "down_weight": (d, F)}


_set("_contrib_GatedFFN", _gated_ffn_shapes)


def _gdn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    Hk, Hv, Dk, Dv = (int(attrs[k]) for k in ("k_heads", "v_heads", "k_dim",
                                              "v_dim"))
    kd, vd = Hk * Dk, Hv * Dv
    return {"qkvz_weight": (2 * kd + 2 * vd, d), "ba_weight": (2 * Hv, d),
            "conv_weight": (2 * kd + vd, int(attrs.get("conv_kernel", 4))),
            "A_log": (Hv,), "dt_bias": (Hv,), "norm_gamma": (Dv,),
            "out_weight": (d, vd)}


_set("_contrib_GatedDeltaNet", _gdn_shapes)


def _kda_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    H, D = int(attrs["heads"]), int(attrs["head_dim"])
    wide = {"%s_weight" % n: (H * D, d) for n in "qkv"}
    return dict(wide, conv_weight=(3 * H * D, int(attrs.get("conv_kernel", 4))),
                fa_weight=(D, d), fb_weight=(H * D, D), A_log=(H,),
                dt_bias=(H * D,), b_weight=(H, d), ga_weight=(D, d),
                gb_weight=(H * D, D), gb_bias=(H * D,), norm_gamma=(D,),
                o_weight=(d, H * D))


_set("_contrib_KimiDeltaAttention", _kda_shapes)


def _cca_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    Hq, Hk, D = (int(attrs[k]) for k in ("q_heads", "kv_heads", "head_dim"))
    H = Hq + Hk
    return {"q_weight": (Hq * D, d), "k_weight": (Hk * D, d),
            "v_weight": (2 * D, d),
            "conv0_weight": (H * D, int(attrs.get("conv_k0", 2))),
            "conv1_weight": (H, D, D, int(attrs.get("conv_k1", 2))),
            "temp": (Hk,), "o_weight": (d, Hq * D)}


_set("_contrib_CompressedConvAttention", _cca_shapes)


def _rms_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    return {"gamma": (data[int(attrs.get("axis", -1)) % len(data)],)}


_set("RMSNorm", _rms_shapes)


def _fused_attn_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    d = int(data[-1])
    return {"qkv_weight": (3 * d, d), "qkv_bias": (3 * d,),
            "proj_weight": (d, d), "proj_bias": (d,)}


_set("_contrib_FusedCausalSelfAttention", _fused_attn_shapes)
# the paged decode/prefill ops share the fused op's projection-weight
# layout; cache/table/position shapes come from bind-time inputs or
# explicit Variable shapes, never from inference
_set("_contrib_PagedDecodeAttention", _fused_attn_shapes)
_set("_contrib_PagedPrefillAttention", _fused_attn_shapes)
_set("_contrib_PagedChunkPrefillAttention", _fused_attn_shapes)


def _ln_shapes(known, attrs):
    data = known.get("data")
    if data is None:
        return {}
    ax = int(attrs.get("axis", -1)) % len(data)
    return {"gamma": (data[ax],), "beta": (data[ax],)}


_set("LayerNorm", _ln_shapes)
_set("InstanceNorm", lambda known, attrs: (
    {"gamma": (known["data"][1],), "beta": (known["data"][1],)}
    if known.get("data") is not None else {}))


_set("Embedding", lambda known, attrs: {
    "weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))})
_set("_contrib_ShardedEmbedding", lambda known, attrs: {
    "weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))})


def _leaky_shapes(known, attrs):
    data = known.get("data")
    if attrs.get("act_type") == "prelu" and data is not None:
        return {"gamma": (data[1],)}
    return {}


_set("LeakyReLU", _leaky_shapes,
     lambda attrs: set() if attrs.get("act_type") == "prelu" else {"gamma"})


def _rnn_shapes(known, attrs):
    data = known.get("data")
    out = {}
    mode = attrs.get("mode", "lstm")
    L = int(attrs["num_layers"])
    H = int(attrs["state_size"])
    bi = bool(attrs.get("bidirectional", False))
    ndir = 2 if bi else 1
    if data is not None:
        out["parameters"] = (rnn_param_size(L, int(data[2]), H, bi, mode),)
        out["state"] = (L * ndir, int(data[1]), H)
        if mode == "lstm":
            out["state_cell"] = (L * ndir, int(data[1]), H)
    return out


_set("RNN", _rnn_shapes,
     lambda attrs: set() if attrs.get("mode", "lstm") == "lstm" else {"state_cell"})

def _softmax_output_shapes(known, attrs):
    d = known.get("data")
    if d is None:
        return {}
    if attrs.get("multi_output"):
        return {"label": (d[0],) + tuple(d[2:])}
    return {"label": tuple(d[:-1])}


_set("SoftmaxOutput", _softmax_output_shapes)
for _nm in ("LinearRegressionOutput", "MAERegressionOutput",
            "LogisticRegressionOutput"):
    _set(_nm, lambda known, attrs: (
        {"label": known["data"]} if known.get("data") is not None else {}))

_set("SequenceMask",
     unused_inputs=lambda attrs: set() if attrs.get("use_sequence_length") else {"sequence_length"})
_set("SequenceLast",
     unused_inputs=lambda attrs: set() if attrs.get("use_sequence_length") else {"sequence_length"})
_set("SequenceReverse",
     unused_inputs=lambda attrs: set() if attrs.get("use_sequence_length") else {"sequence_length"})


# same weight/bias shapes as Convolution (offset is a data input)
_set("_contrib_DeformableConvolution", _conv_shapes,
     lambda attrs: {"bias"} if attrs.get("no_bias") else set())
_set("_contrib_DeformablePSROIPooling",
     unused_inputs=lambda attrs: {"trans"} if attrs.get("no_trans") else set())
