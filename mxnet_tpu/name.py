"""Name manager (reference python/mxnet/name.py): deterministic auto-name
scopes for symbols. ``with mx.name.NameManager():`` resets the counter
scope so generated names ("fullyconnected0"...) restart — what the
reference's fluent-API tests rely on for reproducible graphs.  The
managers are ``base``'s, the ones symbol creation asks."""
from __future__ import annotations

from .base import NameManager, Prefix, current_name_manager as current

__all__ = ["NameManager", "Prefix", "current"]
