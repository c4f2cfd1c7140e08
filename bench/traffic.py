"""The one general traffic generator; each mix is a data file of parameters.

A mix (``bench/traffic/<name>.json``) says how requests arrive and what
they ask for:

* ``loop``: ``"closed"`` (one client sends its next request when the last
  one is answered) or ``"open"`` (requests arrive on a schedule at
  ``rate_per_s`` on average, whatever the system does).  An open mix may
  add ``burst: {"on_s", "off_s"}``: arrivals come only in the on phases,
  faster by ``(on_s + off_s) / on_s``, so the mean rate stays
  ``rate_per_s``.
* ``templates``: the requests, each a template with ``ops`` (per-field
  operation names: ``mean``, ``std``, ``derivative<axis>``, ``gradient``,
  ``laplacian``), ``fields`` (indices, ``"all"``, or ``{"zipf": s}``: one
  field per request, drawn Zipf(s) over the configuration's fields),
  optional ``vector`` (``[{"op": "divergence" | "curl", "components":
  [indices]}]``, one answer over the listed fields), ``stage`` (``"auto"``
  or a stage name), optional ``region`` (a name in the mix's ``regions``,
  or ``{"zipf": s}`` over them in their listed order) and ``weight``
  (default 1).
* ``order``: ``"cycle"`` (the templates in turn, from an offset drawn from
  the seed; the default) or ``"shuffle"``.
* ``block`` (default 256): with ``"shuffle"``, and for every Zipf draw and
  every open-loop gap, each block of that many requests holds the same
  multiset (the law's quantiles: templates in proportion to their weights,
  fields and regions by their Zipf law, exponential gaps), which the seed
  permutes.  So every seed offers the same work in another order.
* ``regions``: named windows ``{name: [[start, stop] per axis]}`` at the
  configuration's published dims.
* ``sample``: how many of a run's answered requests the comparison checks.
"""
from __future__ import annotations

import itertools
from collections.abc import Iterator
from typing import NamedTuple

import numpy as np

DEFAULT_BLOCK = 256


class Template(NamedTuple):
    """One concrete request: every draw already made."""

    ops: tuple[str, ...]
    fields: tuple[int, ...]
    stage: str
    region: str | None = None
    vector: tuple[tuple[str, tuple[int, ...]], ...] = ()

    def all_fields(self) -> tuple[int, ...]:
        """Every distinct field the request reads, in first-use order."""
        comps = (c for _, cs in self.vector for c in cs)
        return tuple(dict.fromkeys(itertools.chain(self.fields, comps)))


def _quantiles(weights, total: int) -> np.ndarray:
    """Item index for each of ``total`` slots: the quantiles of the law
    with these ``weights`` at ``(i + 0.5) / total``, so the counts follow
    the law exactly."""
    p = np.asarray(weights, np.float64)
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(total) + 0.5) / total
    return np.minimum(np.searchsorted(cdf, u), len(p) - 1)


def _zipf(n_items: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, n_items + 1) ** float(s)


def _vector(spec: dict) -> tuple[tuple[str, tuple[int, ...]], ...]:
    return tuple((v["op"], tuple(int(c) for c in v["components"]))
                 for v in spec.get("vector", ()))


def _choices(spec: dict, mix: dict, n_fields: int):
    """The field tuples and regions a template spec can produce, and the
    Zipf exponent of each draw (``None`` where nothing is drawn)."""
    f = spec.get("fields", [])
    if isinstance(f, dict):
        fields, f_s = [(i,) for i in range(n_fields)], float(f["zipf"])
    else:
        fields = [tuple(range(n_fields)) if f == "all" else tuple(f)]
        f_s = None
    r = spec.get("region")
    if isinstance(r, dict):
        regions, r_s = list(mix["regions"]), float(r["zipf"])
    else:
        regions, r_s = [r], None
    return fields, f_s, regions, r_s


def templates(mix: dict, n_fields: int) -> list[Template]:
    """Every distinct request the mix can send (for warm-up and checks)."""
    out = []
    for spec in mix["templates"]:
        fields, _, regions, _ = _choices(spec, mix, n_fields)
        for f, r in itertools.product(fields, regions):
            out.append(Template(tuple(spec.get("ops", ())), f,
                                spec.get("stage", "auto"), r, _vector(spec)))
    return list(dict.fromkeys(out))


def _block_draws(rng, law, block: int) -> Iterator[int]:
    """Endless item indices, each block of ``block`` the quantiles of
    ``law`` (the items' weights), permuted by ``rng``."""
    base = _quantiles(law, block)
    while True:
        yield from (int(i) for i in rng.permutation(base))


def _arrivals(mix: dict, rng, block: int) -> Iterator[float | None]:
    """Gaps between arrivals, in seconds (``None`` for a closed loop)."""
    if mix["loop"] == "closed":
        yield from itertools.repeat(None)
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    u = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-u) / float(mix["rate_per_s"])
    burst = mix.get("burst")
    if burst:
        on, off = float(burst["on_s"]), float(burst["off_s"])
        gaps = gaps * on / (on + off)
    active = wall = 0.0
    while True:
        for g in rng.permutation(gaps):
            if not burst:
                yield float(g)
                continue
            active += float(g)
            cycles, into = divmod(active, on)
            nxt = cycles * (on + off) + into
            yield nxt - wall
            wall = nxt


def requests(mix: dict, n_fields: int, seed: int,
             stream: int = 0) -> Iterator[tuple[float | None, Template]]:
    """Endless ``(gap_s, template)`` pairs; ``gap_s`` is the time after the
    previous arrival (open loop) or ``None`` (closed loop).  ``stream``
    separates independent streams of one seed (warm-up, window)."""
    rng = np.random.default_rng([seed % (1 << 64), stream])
    specs = mix["templates"]
    block = int(mix.get("block", DEFAULT_BLOCK))
    order = mix.get("order", "cycle")
    if order == "cycle":
        start = int(rng.integers(len(specs)))
        which = (i % len(specs) for i in itertools.count(start))
    elif order == "shuffle":
        which = _block_draws(rng, [s.get("weight", 1.0) for s in specs],
                             block)
    else:
        raise ValueError(f"unknown order {order!r}")
    choices = [_choices(s, mix, n_fields) for s in specs]
    draws = {}
    for fields, f_s, regions, r_s in choices:
        for key, n, s in (("f", len(fields), f_s), ("r", len(regions), r_s)):
            if s is not None and (key, n, s) not in draws:
                draws[key, n, s] = _block_draws(rng, _zipf(n, s), block)
    gaps = _arrivals(mix, rng, block)
    for i in which:
        spec, (fields, f_s, regions, r_s) = specs[i], choices[i]
        f = fields[0 if f_s is None else next(draws["f", len(fields), f_s])]
        r = regions[0 if r_s is None else next(draws["r", len(regions), r_s])]
        yield next(gaps), Template(tuple(spec.get("ops", ())), f,
                                   spec.get("stage", "auto"), r,
                                   _vector(spec))


def region_bounds(mix: dict, name: str | None, dims, full_dims):
    """Per-axis ``(start, stop)`` of a named region, scaled from the
    configuration's published dims to ``dims`` (equal outside rehearsal)."""
    if name is None:
        return None
    out = []
    for (a, b), d, full in zip(mix["regions"][name], dims, full_dims):
        lo = a * d // full
        out.append((lo, min(d, max(lo + 3, b * d // full))))
    return tuple(out)
