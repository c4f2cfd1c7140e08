"""Scientific-field data pipeline (the paper's own domain, §VI-A).

Synthesizes deterministic analogues of the paper's five benchmark datasets
(multi-scale smooth structure + noise, matching dims up to a scale factor),
stores them as HSZ-compressed shards, and serves analytics/training
consumers through *homomorphic* accessors: normalization statistics come
from stage-① metadata, derivative/divergence feature channels from stage-③
integers — full decompression only when a consumer asks for raw floats.
"""
from __future__ import annotations
from collections.abc import Iterator

import dataclasses
import os
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (Stage, by_name, encode as hsz_encode, homomorphic)

# name -> (fields, full dims); scale divides each dim for CI-sized runs
DATASETS = {
    "Ocean": (2, (2400, 3600)),
    "Miranda": (7, (256, 384, 384)),
    "Hurricane": (13, (100, 500, 500)),
    "NYX": (6, (512, 512, 512)),
    "JHTDB": (3, (2580, 2580, 2580)),
}


def field_seed(name: str, field: int, seed: int = 0) -> int:
    """RNG seed of one synthetic field, stable across processes (``hash``
    of a ``str`` is randomized per process, ``crc32`` is not)."""
    return zlib.crc32(f"{name}/{field}/{seed}".encode())


def synth_field(name: str, field: int, dims: tuple[int, ...], seed: int = 0) -> np.ndarray:
    """Multi-scale smooth field + noise (compression behaviour like real data).

    Each octave is a sum of one sine per axis, so the sines are evaluated
    on the 1-D axes and broadcast: the same values as on a full meshgrid,
    without building one (a 512^3 field stays a few host arrays)."""
    rng = np.random.default_rng(field_seed(name, field, seed))
    axes = [np.linspace(0, 1, d, dtype=np.float32) for d in dims]
    out = np.zeros(dims, np.float32)
    for k in range(1, 5):  # superposed octaves
        phase = rng.uniform(0, 2 * np.pi, size=len(dims))
        freq = rng.uniform(1.5, 4.0) * (2.0 ** k)
        wave = np.zeros(dims, np.float32)
        for a, (g, ph) in enumerate(zip(axes, phase)):
            line = np.sin(2 * np.pi * freq * g + ph).astype(np.float32)
            wave = wave + line.reshape([-1 if i == a else 1
                                        for i in range(len(dims))])
        out += wave / (2.0 ** k)
    out += rng.normal(0, 0.02, dims).astype(np.float32)
    return out


def dataset_dims(name: str, scale: int = 1) -> tuple[int, ...]:
    _, dims = DATASETS[name]
    return tuple(max(8, d // scale) for d in dims)


@dataclasses.dataclass
class CompressedShard:
    dataset: str
    field: int
    blob: bytes

    def open(self):
        return hsz_encode.deserialize(self.blob)


class ScientificStore:
    """In-memory/on-disk store of HSZ-compressed field shards."""

    def __init__(self, compressor_name: str = "hszp_nd", rel_eb: float = 1e-3,
                 scale: int = 8, seed: int = 0, root: str | None = None):
        self.comp_name = compressor_name
        self.rel_eb = rel_eb
        self.scale = scale
        self.seed = seed
        self.root = root
        self._cache: dict[tuple[str, int], CompressedShard] = {}

    def _compressor(self, ndim: int):
        name = self.comp_name
        if name.endswith("_nd"):
            return by_name(name)
        return by_name(name)

    def put_all(self, datasets: list[str] | None = None):
        for name in datasets or DATASETS:
            fields, _ = DATASETS[name]
            for f in range(fields):
                self.get(name, f)

    def get(self, dataset: str, field: int) -> CompressedShard:
        key = (dataset, field)
        if key in self._cache:
            return self._cache[key]
        if self.root:
            path = os.path.join(self.root, f"{dataset}_{field}.hsz")
            if os.path.exists(path):
                with open(path, "rb") as fh:
                    shard = CompressedShard(dataset, field, fh.read())
                self._cache[key] = shard
                return shard
        dims = dataset_dims(dataset, self.scale)
        data = synth_field(dataset, field, dims, self.seed)
        comp = self._compressor(len(dims))
        c = comp.compress(jnp.asarray(data), rel_eb=self.rel_eb)
        blob = hsz_encode.serialize(c)
        shard = CompressedShard(dataset, field, blob)
        if self.root:
            os.makedirs(self.root, exist_ok=True)
            with open(os.path.join(self.root, f"{dataset}_{field}.hsz"), "wb") as fh:
                fh.write(blob)
        self._cache[key] = shard
        return shard

    # -- homomorphic accessors (never decompress further than needed) -------
    def stats(self, dataset: str, field: int) -> dict[str, float]:
        c = self.get(dataset, field).open()
        stage = Stage.M if c.scheme.is_blockmean else Stage.P
        return {"mean": float(homomorphic.mean(c, stage)),
                "std": float(homomorphic.std(c, Stage.P))}

    def derivative_features(self, dataset: str, field: int, stage: Stage = Stage.Q):
        c = self.get(dataset, field).open()
        return homomorphic.gradient(c, stage)

    def raw(self, dataset: str, field: int) -> jax.Array:
        c = self.get(dataset, field).open()
        comp = self._compressor(len(c.shape))
        return comp.decompress(c, Stage.F)

    def normalized_batches(self, dataset: str, field: int, batch: int,
                           patch: tuple[int, ...] = (64, 64)) -> Iterator[np.ndarray]:
        """Training-style consumer: patches normalized by homomorphic stats."""
        st = self.stats(dataset, field)
        arr = np.asarray(self.raw(dataset, field))
        arr = (arr - st["mean"]) / max(st["std"], 1e-9)
        flat_dims = arr.shape[:2] if arr.ndim >= 2 else arr.shape
        rng = np.random.default_rng(0)
        while True:
            coords = [rng.integers(0, max(1, s - p), size=batch)
                      for s, p in zip(arr.shape, patch)]
            out = np.stack([
                arr[tuple(slice(c[i], c[i] + p) for c, p in zip(coords, patch))]
                for i in range(batch)])
            yield out
