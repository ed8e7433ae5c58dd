"""Plan/execute GEMM dispatch — the one surface for every GEMM this repo
runs (replaces the three ad-hoc entry points in ``core/panel_gemm``).

    from repro import gemm

    p = gemm.plan(m, n, k)                 # shape-resolved policy + cache
    pw = gemm.pack_for_plan(p, w)          # pay the pack once (lever 2)
    y = gemm.execute(p, x, pw)             # per call: compute loop only

See ``docs/gemm_api.md`` for the policy table, cache semantics, backend
registry, the quantized ``weight_format`` plans (docs/quantization.md),
and the migration table for the REMOVED ``core.panel_gemm`` shims.
"""
from repro.gemm.backends import (Backend, UnknownBackendError,
                                 default_backend, get_backend,
                                 list_backends, register_backend,
                                 unregister_backend, use_backend)
from repro.gemm.execute import (PlanMismatchError, execute, lead_m,
                                pack_for_plan, split_fused, validate_plan)
from repro.gemm.plan import (EpilogueSpec, GemmPlan, LEVER_FINE_PANELS,
                             LEVER_PREPACK, PACK_NONE, PACK_PERCALL,
                             PACK_PREPACKED)
from repro.gemm.plan_store import (PlanStore, StoreInfo, SCHEMA_VERSION,
                                   active_plan_store, as_plan_store,
                                   host_fingerprint, no_plan_store,
                                   plan_store_info, set_plan_store,
                                   use_plan_store)
from repro.gemm.policy import (DECODE_M_BUCKETS, DECODE_SPLIT_K_CANDIDATES,
                               DEFAULT_NUM_CORES, PREFILL_M_BUCKETS,
                               bucket_m, decode_lane, in_decode_lane,
                               pack_blocks, plan, plan_cache_clear,
                               plan_cache_info, plan_for_packed,
                               sparse_threshold, store_key,
                               vmem_clamped_count)
from repro.kernels.panel_gemm import apply_epilogue, splitk_combine

__all__ = [
    "Backend", "EpilogueSpec", "GemmPlan", "PlanMismatchError",
    "PlanStore", "StoreInfo", "SCHEMA_VERSION",
    "UnknownBackendError",
    "LEVER_FINE_PANELS", "LEVER_PREPACK", "DEFAULT_NUM_CORES",
    "PACK_NONE", "PACK_PERCALL", "PACK_PREPACKED", "PREFILL_M_BUCKETS",
    "DECODE_M_BUCKETS", "DECODE_SPLIT_K_CANDIDATES",
    "active_plan_store", "apply_epilogue", "as_plan_store", "bucket_m",
    "decode_lane", "default_backend", "execute", "get_backend",
    "host_fingerprint", "in_decode_lane", "lead_m", "list_backends",
    "no_plan_store", "pack_blocks", "pack_for_plan", "plan",
    "plan_cache_clear", "plan_cache_info", "plan_for_packed",
    "plan_store_info", "register_backend",
    "set_plan_store", "sparse_threshold", "split_fused",
    "splitk_combine", "store_key",
    "unregister_backend", "use_backend", "use_plan_store",
    "validate_plan", "vmem_clamped_count",
]
