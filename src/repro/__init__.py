"""HSZ: homomorphic analytical operations on compressed scientific data,
integrated as a first-class feature of a multi-pod JAX LM framework.

Public entry points:

    repro.core       — the paper: 4 compressors, 4 stages, 6 homomorphic ops
    repro.kernels    — Pallas TPU kernels (ops.py wrappers / ref.py oracles)
    repro.models     — 10-architecture zoo behind one functional facade
    repro.comm       — homomorphic compressed collectives (int16 grad sync)
    repro.train      — optimizer / train-step builder / HSZ checkpoints
    repro.serve      — batched decode engine (int8 KV residency)
    repro.store      — materialized-stage field store (id-addressed serving)
    repro.stream     — streaming time-slab ingest + incremental temporal analytics
    repro.obs        — spans and counters of the served path (ring + profiler)
    repro.data       — resumable token pipeline + compressed field store
    repro.configs    — assigned architectures x shapes registry
    repro.launch     — mesh rules, multi-pod dry-run, roofline, drivers
"""

__version__ = "1.0.0"
