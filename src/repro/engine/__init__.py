"""Unified checked-op engine: backend-dispatched layers, sharding, batching.

Public surface:
  api       — Graph, gcn_layer, gcn_forward, gcn_apply, gcn_loss (the one
              entry point for a checked GCN)
  backends  — AggregationBackend (a CheckedOp) + dense/bcoo/block_ell registry
  sharded   — Partition + shard_map'd stripe-sharded block-ELL aggregation
  batching  — bucketed padding of variable-size graphs for batched serving
  streaming — continuous-traffic serving: canonical rungs, online packing,
              double-buffered guarded dispatch, latency SLOs, backpressure
  lm        — guarded transformer LM serving (fold_lm_w_r, LMEngine)
  gat       — guarded GAT serving (attention-weighted aggregation under
              the same eq. 4–6 chain checks)
  selfcheck — the serving loop's check-the-check: periodic re-derivation
              of the eq.-5 fold and the staged s_c
"""
from .api import (  # noqa: F401
    Graph,
    fold_w_r,
    gcn_apply,
    gcn_forward,
    gcn_layer,
    gcn_loss,
)
from .backends import (  # noqa: F401
    AggregationBackend,
    backend_names,
    get_backend,
    infer_backend,
    make_backend,
    register_backend,
)
from .localize import (  # noqa: F401
    gather_stripe_system,
    surgical_stripe_retry,
)
from .batching import (  # noqa: F401
    GraphBatch,
    PackedGraphs,
    graph_pack_stats,
    make_batches,
    make_packed_batches,
    pack_graphs,
    pad_graph,
    pick_bucket,
    schedule_packs,
    synth_graph_stream,
)
from .sharded import (  # noqa: F401
    Partition,
    sharded_gcn_fused,
    sharded_spmm_abft,
    stage_rows,
)
from .selfcheck import (  # noqa: F401
    CheckPathSelfCheck,
    refold,
    verify_s_c,
    verify_w_r,
)
from .streaming import (  # noqa: F401
    PackedRunner,
    RequestResult,
    Rung,
    RungTable,
    StreamingEngine,
    plan_rungs,
)
from .lm import (  # noqa: F401
    LMEngine,
    fold_lm_w_r,
    make_guarded_decode_step,
    make_guarded_prefill_step,
)
from .gat import (  # noqa: F401
    GATEngine,
    gat_forward,
    gat_layer,
    init_gat,
    make_gat_serve_step,
)
