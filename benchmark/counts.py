"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no later PR can change what a roofline
share is measured against.  Hand-worked values at the cells' real sizes
are in tests/benchmark.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in benchmark/peaks.json "
                       "(known: %s)" % (device_kind, sorted(table)))
    return table[device_kind]


# ----------------------------------------------------------------------
# GPT-2-shaped decoder
# ----------------------------------------------------------------------
def lm_dims(cfg):
    d = int(cfg["d_model"])
    return (d, int(cfg.get("ffn_dim") or 4 * d), int(cfg["num_layers"]),
            int(cfg["num_classes"]))


def lm_forward_flops_per_token(cfg, seq_len):
    """Multiply-adds x 2 of one token's forward pass at ``seq_len``:
    per layer qkv 6d^2, proj 2d^2, FFN 4df, causal attention 2*S*d
    (QK^T and PV are 2*S*d each over the full square; causality needs
    half); the head 2dV.  Embedding lookups and elementwise work are
    not counted."""
    d, f, L, V = lm_dims(cfg)
    per_layer = 8 * d * d + 4 * d * f + 2 * int(seq_len) * d
    return L * per_layer + 2 * d * V


def lm_train_flops_per_token(cfg, seq_len):
    """Forward plus backward (twice the forward), no recompute."""
    return 3 * lm_forward_flops_per_token(cfg, seq_len)


def lm_weight_bytes(cfg, bytes_per_weight=2):
    """Bytes of the weights one engine iteration has to read: every
    layer and the head, once.  The embedding tables are gathered by
    row (see :func:`lm_serve_iter_bytes`)."""
    d, f, L, V = lm_dims(cfg)
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (2 * d * f + f + d) \
        + 4 * d
    return (L * per_layer + 2 * d + d * V + V) * bytes_per_weight


def lm_kv_bytes_per_row(cfg, bytes_per_value=2):
    """K and V of one context row over all layers."""
    d, _, L, _ = lm_dims(cfg)
    return 2 * L * d * bytes_per_value


def lm_serve_iter_bytes(cfg, live_rows, tokens_in_iter, embed_bytes=4):
    """Bytes one engine iteration must read: the weights once, the K/V
    rows of the live context of the sequences in it, and one embedding
    and one position row per token it computes."""
    d = lm_dims(cfg)[0]
    return (lm_weight_bytes(cfg) + live_rows * lm_kv_bytes_per_row(cfg)
            + 2 * tokens_in_iter * d * embed_bytes)


# ----------------------------------------------------------------------
# ResNet (bottleneck, ImageNet stem)
# ----------------------------------------------------------------------
RESNET_UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_flops_per_image(num_layers=50, image=224, classes=1000):
    """Multiply-adds x 2 of the convolutions and the classifier of a
    bottleneck ResNet (both versions: the same convolutions).  BatchNorm,
    ReLU and pooling are not counted."""
    units = RESNET_UNITS[int(num_layers)]
    filters = (64, 256, 512, 1024, 2048)
    hw = image // 2                                   # conv0, stride 2
    macs = hw * hw * 7 * 7 * 3 * filters[0]
    hw //= 2                                          # pool0
    cin = filters[0]
    for stage, n in enumerate(units):
        cout = filters[stage + 1]
        mid = cout // 4
        for u in range(n):
            stride = 2 if (u == 0 and stage > 0) else 1
            hw_in, hw_out = hw, hw // stride
            # version 2 (the zoo's default) strides the 3x3, so the
            # first 1x1 still sees the unit's input extent
            macs += hw_in * hw_in * cin * mid                 # conv1 1x1
            macs += hw_out * hw_out * 9 * mid * mid           # conv2 3x3
            macs += hw_out * hw_out * mid * cout              # conv3 1x1
            if u == 0:
                macs += hw_out * hw_out * cin * cout          # shortcut
            cin, hw = cout, hw_out
    macs += cin * classes
    return 2 * macs


def resnet_train_flops_per_image(num_layers=50, image=224, classes=1000):
    return 3 * resnet_forward_flops_per_image(num_layers, image, classes)
