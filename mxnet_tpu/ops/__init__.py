"""Operator corpus — pure-JAX implementations behind the registry.

Importing this package registers every op family (the analog of the
reference's static NNVM_REGISTER_OP initializers across src/operator/).
"""
from . import registry
from .registry import OpDef, get_op, list_ops, register
# Each family module self-registers on import; order only matters for the
# few families that extend earlier ones (shape_rules goes last).
from . import (elemwise, tensor, nn, optimizer_ops, random_ops, rnn,  # noqa: F401
               custom, contrib_ops, quantization_ops, extra, tail_ops,
               rcnn, shape_rules)

__all__ = ["registry", "register", "get_op", "list_ops", "OpDef"]
