"""Public jit'd wrappers over the Pallas kernels, with explicit backend mode.

The kernel backend is resolved **once at import** from the ``REPRO_KERNELS``
environment variable, so a CI run is deterministic end to end instead of
depending on a per-call backend probe:

* ``interpret`` — run every kernel through the Pallas interpreter (the CPU
  CI mode: same kernel code path as TPU, emulated);
* ``native``    — compile kernels for the accelerator (TPU);
* ``off``       — disable kernel *selection*: every call site that gates on
  :func:`kernels_enabled` (the fused lowering rules, the Encoded payload
  decode) takes its plain-XLA fallback instead.  This is what makes A/B
  bit-identity checks forceable from the outside;
* ``auto`` (default) — ``native`` on TPU, ``interpret`` elsewhere.

:func:`override_mode` temporarily rebinds the mode in-process — the fused
bit-identity tests run each cell once per mode and compare.  Anything that
caches a traced program across mode changes must key on
:func:`kernel_mode` (the engine's cache keys do).
"""
from __future__ import annotations

import contextlib
import os

import jax

from . import bitpack as _bitpack
from . import block_stats as _block_stats
from . import fused as _fused
from . import prefix_stats as _prefix_stats
from . import quant_lorenzo as _quant_lorenzo
from . import stencil_dq as _stencil_dq

_MODES = ("auto", "interpret", "native", "off")


def _resolve(raw: str) -> str:
    mode = raw.strip().lower() or "auto"
    if mode not in _MODES:
        raise ValueError(
            f"REPRO_KERNELS={raw!r}: expected one of {_MODES}")
    if mode == "auto":
        return "native" if jax.default_backend() == "tpu" else "interpret"
    return mode


#: resolved once at import (env), rebound only by :func:`override_mode`.
_MODE = _resolve(os.environ.get("REPRO_KERNELS", "auto"))


def kernel_mode() -> str:
    """The resolved backend mode: ``interpret`` | ``native`` | ``off``."""
    return _MODE


def kernels_enabled() -> bool:
    """Should kernel-capable call sites select the Pallas path?"""
    return _MODE != "off"


@contextlib.contextmanager
def override_mode(mode: str):
    """Temporarily force the backend mode (A/B bit-identity checks)."""
    global _MODE
    prev = _MODE
    _MODE = _resolve(mode)
    try:
        yield _MODE
    finally:
        _MODE = prev


def _interpret() -> bool:
    # "off" still runs the kernel when a wrapper is called directly (the
    # wrappers *are* the kernels); selection happens at the call sites.
    return _MODE != "native"


def quant_lorenzo2d(x: jax.Array, eps) -> jax.Array:
    """Fused quantize + 2-D Lorenzo decorrelation (compression hot path)."""
    return _quant_lorenzo.quant_lorenzo2d(x, eps, interpret=_interpret())


def pack(u: jax.Array, bits: int) -> jax.Array:
    return _bitpack.pack(u, bits, interpret=_interpret())


def unpack(words: jax.Array, n: int, bits: int) -> jax.Array:
    return _bitpack.unpack(words, n, bits, interpret=_interpret())


def lorenzo3d_q(payload: jax.Array, shape: tuple, bits: int) -> jax.Array:
    """Stage-③ integers of a 3-D Lorenzo field (padded ``shape``) from its
    packed payload in one pass (the store's materialization)."""
    return _fused.lorenzo3d_q(payload, shape, bits, interpret=_interpret())


def grad2d(q: jax.Array, eps):
    """Fused stage-③ central differences (both axes, one pass)."""
    return _stencil_dq.grad2d(q, eps, interpret=_interpret())


def laplacian2d(q: jax.Array, eps):
    return _stencil_dq.laplacian2d(q, eps, interpret=_interpret())


def block_stats(q_blocked: jax.Array):
    """Per-block (integer mean, zigzag max) metadata reduction."""
    return _block_stats.block_stats(q_blocked, interpret=_interpret())


def prefix_stats2d(p: jax.Array):
    """Algorithm-4 (sum q, sum q^2) from residuals, no reconstruction."""
    return _prefix_stats.prefix_stats2d(p, interpret=_interpret())
