from repro.meshctx import constrain, get_mesh, set_mesh
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    opt_shardings,
    param_shardings,
    replicated,
    rules_for,
    spec_for,
)
from repro.distributed.collectives import (
    LayoutChoice,
    choose_gemm_layout,
    ring_all_gather_s,
    ring_all_reduce_s,
)

__all__ = ["constrain", "get_mesh", "set_mesh", "batch_shardings", "cache_shardings", "opt_shardings",
           "param_shardings", "replicated", "rules_for", "spec_for",
           "LayoutChoice", "choose_gemm_layout", "ring_all_gather_s",
           "ring_all_reduce_s"]
