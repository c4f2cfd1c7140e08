"""Quickstart: compress a scientific field, analyze it without decompressing.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Stage, hszp_nd, hszx_nd, homomorphic as H
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    # a smooth 2-D field with noise (think: sea-surface temperature)
    rng = np.random.default_rng(0)
    g = np.linspace(0, 4 * np.pi, 1200)
    field = (np.sin(g)[:, None] * np.cos(g / 2)[None, :] * 5
             + rng.normal(0, 0.05, (1200, 1200))).astype(np.float32)
    data = jnp.asarray(field)

    print("== compress (HSZx-nd: block-mean metadata -> stage-1 stats) ==")
    c = hszx_nd.compress(data, rel_eb=1e-3)
    print(f"error bound eps = {float(c.eps):.3e}")
    print(f"compression ratio = {float(hszx_nd.compression_ratio(c)):.2f}x")

    print("\n== mean at each decompression stage ==")
    for stage in (Stage.M, Stage.P, Stage.Q, Stage.F):
        fn = jax.jit(lambda cc, s=stage: H.mean(cc, s))
        val = float(fn(c)); jax.block_until_ready(val)
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(fn(c))
        dt = (time.perf_counter() - t0) / 5
        print(f"stage {stage.name}: mean={val:+.6f}   {dt*1e3:7.2f} ms "
              f"({'metadata only!' if stage == Stage.M else ''})")
    print(f"numpy reference: {field.mean():+.6f}")

    print("\n== derivatives straight from quantized integers (HSZp-nd) ==")
    cp = hszp_nd.compress(data, rel_eb=1e-3)
    for stage in (Stage.P, Stage.Q, Stage.F):
        d0 = np.asarray(H.derivative(cp, stage, 0))
        ref = (field[2:, 1:-1] - field[:-2, 1:-1]) / 2
        print(f"stage {stage.name}: max|err| vs raw data = "
              f"{np.abs(d0 - ref).max():.2e} (eps={float(cp.eps):.2e})")


if __name__ == "__main__":
    main()
