"""Symbol-based model zoo.

Reference parity: example/image-classification/symbols/ (mlp, lenet,
alexnet, vgg, resnet, resnext, mobilenet, inception-bn, googlenet,
squeezenet, densenet). Each module exposes ``get_symbol(num_classes, ...)``
returning a Symbol ending in SoftmaxOutput, so any of them drops into
``Module.fit`` / ``benchmark/run.py`` unchanged.  Eight language-model
families beside them, with the same factory signature: ``transformer``
(GPT-2's block), ``zaya`` (compressed convolutional attention and a
dropless top-1 expert sublayer), ``qwen3_next`` (Gated DeltaNet layers
beside gated attention, a dropless top-k sublayer with a shared expert)
``kanana2`` (latent attention, a leading dense layer, sigmoid scores
with a selection bias before the top-k sublayer), ``keye_vl2``
(attention over the keys a learned index scorer picks, trained by a
second loss head, before a softmax top-k sublayer), ``smallthinker``
(window and full grouped-query attention layers in one model, a router
that reads the layer's input, ReLU-gated experts), ``sdar_moe``
(trained by block diffusion: a clean and a noised copy of the sequence
in one pass under a block-structured mask, a masked and weighted loss
head) and ``kimi_linear`` (Kimi Delta Attention, a delta rule gated per
key channel, three layers to one of latent attention without position,
a leading dense layer under a linear mixer); the last seven hand
out the experts' token counts as an output, and share one frame
(``_decoder.py``: ``experts_held``, the embedding, the closing norm, the
head, the loss and the counts' output), so that a family's file is its
mixer, its expert sublayer's attributes and its layer schedule.

These are fresh TPU-first definitions (bf16-friendly: ``dtype`` casts the
trunk while the final classifier/softmax stays fp32), not translations of
the reference scripts.
"""
from . import mlp
from . import lenet
from . import alexnet
from . import vgg
from . import resnet
from . import resnext
from . import mobilenet
from . import inception_bn
from . import googlenet
from . import squeezenet
from . import densenet
from . import transformer
from . import zaya
from . import qwen3_next
from . import kanana2
from . import keye_vl2
from . import smallthinker
from . import sdar_moe
from . import kimi_linear

_NETWORKS = {
    "transformer": transformer,
    "zaya": zaya,
    "qwen3_next": qwen3_next,
    "kanana2": kanana2,
    "keye_vl2": keye_vl2,
    "smallthinker": smallthinker,
    "sdar_moe": sdar_moe,
    "kimi_linear": kimi_linear,
    "mlp": mlp,
    "lenet": lenet,
    "alexnet": alexnet,
    "vgg": vgg,
    "resnet": resnet,
    "resnext": resnext,
    "mobilenet": mobilenet,
    "inception-bn": inception_bn,
    "inception_bn": inception_bn,
    "googlenet": googlenet,
    "squeezenet": squeezenet,
    "densenet": densenet,
}


def get_symbol(network, **kwargs):
    """Factory mirroring example/image-classification/common/fit.py usage:
    ``models.get_symbol('resnet', num_classes=1000, num_layers=50,
    image_shape=(3,224,224))``."""
    if network not in _NETWORKS:
        raise ValueError("unknown network '%s'; available: %s"
                         % (network, sorted(set(_NETWORKS))))
    return _NETWORKS[network].get_symbol(**kwargs)
