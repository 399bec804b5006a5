"""mx.embedding — device-sharded embedding tables + a compiled
row_sparse gradient pipeline (docs/EMBEDDING.md).

The recommendation-scale workload (DLRM-style models: embedding-
dominated FLOPs, heavy-tailed index traffic) threaded through every
layer that already exists:

* ``ShardedEmbedding`` (block.py) — gluon block whose table
  row-partitions over the local device mesh (sharding.py) and whose
  lookup is ONE compiled gather program per step (lookup.py);
* ``SparseApplyEngine`` (engine.py) — the kvstore's compiled
  dedup/coalesce -> 2-bit-compress -> cross-host-reduce -> lazy
  sparse-apply program per table, routed automatically by
  ``kv.push`` for row_sparse gradients when the optimizer implements
  ``_fused_sparse_sig`` (SGD, AdaGrad, GroupAdaGrad);
* sharded-table checkpoints (checkpoint.py) — each rank persists its
  owned row range under the PR 7 manifest protocol;
* ``tests/test_embedding.py`` exercises the whole stack and pins at
  most two sparse dispatches a step and zero steady-state retraces.

The symbol-level twin is the ``_contrib_ShardedEmbedding`` op
(ops/nn.py) for compiled module graphs.
"""
from . import sharding
from . import lookup
from . import engine
from . import block
from . import checkpoint
from .sharding import row_range, local_mesh, place_table
from .lookup import lookup as lookup_rows
from .engine import SparseApplyEngine
from .block import ShardedEmbedding
from .checkpoint import (save_tables, load_tables, latest_tables,
                         list_table_tags)

__all__ = ["ShardedEmbedding", "SparseApplyEngine", "row_range",
           "local_mesh", "place_table", "lookup_rows", "save_tables",
           "load_tables", "latest_tables", "list_table_tags",
           "sharding", "lookup", "engine", "block", "checkpoint"]
