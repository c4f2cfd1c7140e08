"""HSZ: homomorphic analytical operations on compressed scientific data,
served from compressed field stores on one chip or sharded over several.

Public entry points:

    repro.core       — the paper: 4 compressors, 4 stages, 6 homomorphic ops
    repro.kernels    — Pallas TPU kernels (ops.py wrappers / ref.py oracles)
    repro.analytics  — query planner, cost model, batched jit engine
    repro.store      — materialized-stage field store (id-addressed serving)
    repro.stream     — streaming time-slab ingest + incremental temporal analytics
    repro.shard      — block-sharded field store over a 1-D device mesh
    repro.serve      — analytics frontend (batched requests, appends, routing)
    repro.comm       — homomorphic compressed collectives (int16 psum budget)
    repro.obs        — spans and counters of the served path (ring + profiler)
    repro.data       — synthetic scientific fields + compressed field store
    repro.audit      — static invariant audit (int widths, kernels, shards)
    repro.launch     — compile cache, analytics mesh, HLO/jaxpr byte counts
"""

__version__ = "1.0.0"
