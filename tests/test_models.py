"""Model-zoo construction + tiny forward/train smoke tests.

Mirrors the reference's symbol tests (tests/python/unittest/test_symbol.py)
and the train-integration tier (tests/python/train/) at toy scale.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


ALL_NETS = [
    ("mlp", {"num_classes": 10}),
    ("lenet", {"num_classes": 10}),
    ("alexnet", {"num_classes": 17}),
    ("vgg", {"num_classes": 17, "num_layers": 11}),
    ("resnet", {"num_classes": 17, "num_layers": 18}),
    ("resnet", {"num_classes": 17, "num_layers": 50}),
    ("resnext", {"num_classes": 17, "num_layers": 50}),
    ("mobilenet", {"num_classes": 17}),
    ("inception-bn", {"num_classes": 17}),
    ("googlenet", {"num_classes": 17}),
    ("squeezenet", {"num_classes": 17}),
    ("densenet", {"num_classes": 17, "num_layers": 121}),
]


@pytest.mark.parametrize("net,kw", ALL_NETS,
                         ids=["%s-%s" % (n, k.get("num_layers", "")) for n, k in ALL_NETS])
def test_build_and_infer(net, kw):
    s = models.get_symbol(net, **kw)
    if net in ("mlp",):
        dshape = (2, 784)
    elif net == "lenet":
        dshape = (2, 1, 28, 28)
    else:
        dshape = (2, 3, 224, 224)
    arg_shapes, out_shapes, aux_shapes = s.infer_shape(data=dshape)
    assert out_shapes[0] == (2, kw["num_classes"])
    assert all(sh is not None for sh in arg_shapes)


def test_resnet50_forward():
    s = models.get_symbol("resnet", num_classes=10, num_layers=50,
                          image_shape=(3, 32, 32))
    ex = s.simple_bind(ctx=mx.cpu(), data=(2, 3, 32, 32),
                       softmax_label=(2,), grad_req="null")
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = np.random.uniform(-0.05, 0.05, arr.shape)
    out = ex.forward(is_train=False, data=np.random.uniform(
        0, 1, (2, 3, 32, 32)).astype(np.float32))
    p = out[0].asnumpy()
    assert p.shape == (2, 10)
    np.testing.assert_allclose(p.sum(axis=1), np.ones(2), rtol=1e-4)


def test_cifar_resnet_depth():
    s = models.get_symbol("resnet", num_classes=10, num_layers=20,
                          image_shape=(3, 28, 28))
    args, outs, _ = s.infer_shape(data=(4, 3, 28, 28))
    assert outs[0] == (4, 10)


def test_json_roundtrip_resnet():
    s = models.get_symbol("resnet", num_classes=10, num_layers=18)
    s2 = mx.sym.load_json(s.tojson())
    assert s2.list_arguments() == s.list_arguments()
    assert s2.list_outputs() == s.list_outputs()


# sha256 of ``tojson()`` at commit e95a1bd (PR 42), before the five
# builders shared ``models/_decoder.py``, each under a fresh name
# manager: at the cell's configuration and at the small one its
# rehearsal and tests use
DECODER_GRAPHS = {
    ("zaya", "zaya1_8b_train"): (
        "e10aaa1f7dc6dfb5e456e809649854b0a003cf8eb3daf2015f59d67f72321743",
        "967bd32081615169f7797230bc689435a110c1c4fd46e045644772ba94d4b5f4"),
    ("qwen3_next", "qwen3_next_80b_train"): (
        "354bc227a23c4d891976bc9f1d64f699ef21d4017dba8a8ef6e30e0335ceba7a",
        "be6b0de47a008dffef5c78038f64c38b67e965d56112218fc10e23839e49f3ee"),
    ("kanana2", "kanana2_30b_train"): (
        "450087a279f6db515ee99f11ec377f1677dbcaa5ed71f944cd32b790f3353dbb",
        "d3e1081e6d3d95625414e9a1907fc75a86dbee98e0c39fc7f168ee7363301930"),
    ("keye_vl2", "keye_vl2_30b_train"): (
        "484d6392b47813ed92bd64184f486c73015c3af9f4d599a9f333595ba4787b45",
        "2f7403294440dbd274beb9b7b47f51a9ae879074eccc7e45b2d86ea8f804f180"),
    ("smallthinker", "smallthinker_21b_train"): (
        "f14da0925abf9e134d97a5a7f65afea7534e554dedbc627824e2ba67b2f5c573",
        "1fbbefe52c3082be398d3116ae384c4a8373d88ff99fc816d124da3bbfbbb3fc"),
    # the seventh family, as PR 44 built it on the frame (its review
    # round: a float32 stream, the routers on float32 rows, rows_slack)
    ("sdar_moe", "sdar_30b_a3b_train"): (
        "dcd0ecf0e73bd67504aa181928423d5f31d7a2031c709979af1bdcfeea6365da",
        "09e4fd547b4f4c4963d5562384de8e2881e2424d2c68954ba9576fefb03359b2"),
    # the eighth family, as PR 47 built it on the frame
    ("kimi_linear", "kimi_linear_48b_train"): (
        "fb6e423e295deb808de5baffa7d9aecbe16a1996d1434e3397fd72d7b8e5cc21",
        "3ce5af78ea4bcd1582c58e1dc7e9d5e0f8c0663cc3562940a84ac8723b3850fe"),
}


@pytest.mark.parametrize("size", ["cell", "small"])
@pytest.mark.parametrize("family,config", list(DECODER_GRAPHS))
def test_a_decoder_family_builds_the_graph_it_built(family, config, size):
    """The frame (``models/_decoder.py``) names and orders every node,
    argument and attribute as the family's own file did: weights are
    matched to the float32 reference by name, checkpoints name them, and
    the compile cache is keyed by the program.  A digest that moves says
    the graph moved: record a new one only with a reason to move it."""
    import hashlib
    import json
    import os
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    assert cfg["model"] == family
    kw = cfg["kwargs"] if size == "cell" else cfg["rehearse"]["kwargs"]
    with mx.name.NameManager():     # auto-named nodes count from 0
        graph = models.get_symbol(family, **kw).tojson()
    assert hashlib.sha256(graph.encode()).hexdigest() \
        == DECODER_GRAPHS[family, config][size == "small"]


def _small_kwargs(config):
    import json
    import os
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        return dict(json.load(f)["rehearse"]["kwargs"])


@pytest.mark.parametrize("held,want", [
    (None, (0, None)), (3, (0, 3)), ([1, 3], (1, 3)), ((2, 2), (2, 2))],
    ids=["all", "from-expert-0", "first-and-count", "a-tuple"])
@pytest.mark.parametrize("family,config", list(DECODER_GRAPHS))
def test_the_frame_reads_experts_held_for_every_family(family, config, held,
                                                       want):
    """``experts_held`` is parsed in one place, the frame: absent it is
    every expert, a number counts from expert 0, a pair is (first,
    count).  Every expert sublayer of every family gets the same share,
    and its three stacks are ``count`` experts tall."""
    import json
    kw = dict(_small_kwargs(config), experts_held=held)
    net = models.get_symbol(family, **kw)
    first, count = want[0], want[1] or kw["num_experts"]
    layers = [n for n in json.loads(net.tojson())["nodes"]
              if n["op"] == "_contrib_RoutedExperts"]
    assert layers
    for n in layers:
        assert (int(n["attrs"]["held_first"]),
                int(n["attrs"]["held_count"])) == (first, count)
    S = kw["seq_len"]
    # the seventh family's data is ids, noised ids and weights
    data = (1, 3, S) if family == "sdar_moe" else (1, S)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=data)[0]))
    stacks = [s for a, s in shapes.items()
              if a.endswith(("moe_gate_weight", "moe_up_weight",
                             "moe_down_weight"))]
    assert len(stacks) == 3 * len(layers)
    assert {s[0] for s in stacks} == {count}


@pytest.mark.parametrize("held", [0, [0, 0], [-1, 2], "past-the-last"],
                         ids=["none", "an-empty-share", "before-expert-0",
                              "past-the-last"])
@pytest.mark.parametrize("family,config", list(DECODER_GRAPHS))
def test_the_frame_refuses_a_share_that_is_no_part_of_the_experts(
        family, config, held):
    """A share with no expert, or one that reaches outside 0..E-1, is a
    ``ValueError`` that names what was asked and how many experts there
    are, in every family (each file had its own copy of the check)."""
    kw = _small_kwargs(config)
    E = kw["num_experts"]
    if held == "past-the-last":
        held = [E - 1, 2]
    with pytest.raises(ValueError, match="no part of %d experts" % E):
        models.get_symbol(family, **dict(kw, experts_held=held))
