"""Plain reference of the timeloop-like cost of one mapping of a GEMM.

It imports nothing of the program. The architecture is the copy in the
traffic file, the problem is ``Out[b,o] += In0[b,i] * In1[i,o]``, and the
mapping is the dict a mapping query answers with: per level, outermost
first, ``temporal_tile_sizes``, ``spatial_tile_sizes`` and
``temporal_order``. The model, level by level:

- Level ``l`` iterates ``outer // tt`` times per dim (``outer`` is the
  problem size at the top, then the level above's spatial tile), in the
  declared order with undeclared dims after it in problem order, and
  spreads ``tt // st`` copies per dim over its sub-clusters.
- Compute cycles: all temporal trips times ``ceil(leaf tile MACs /
  MACs per cycle)``.
- A data space's tile at a buffered level ``l`` is the product of its dims'
  ``tt``. It is refilled once per change of its relevant indices among the
  temporal loops at levels ``<= l``: the product of the relevant trips
  times the irrelevant trips that enclose a relevant one. An output also
  drains on each change, and re-reads (read-modify-write) each change
  beyond the first visit of every tile.
- The nearest buffered level above serves those fills once per distinct
  relevant spatial copy between the two levels (ideal multicast).
- Latency: the larger of compute and, per buffered level below the top
  with finite bandwidth, its per-instance fill and drain bytes over that
  bandwidth. Energy: fills as writes and drains as reads of the level,
  the parent's reads and writes, the innermost operand accesses (inputs
  once per MAC, the output twice) and the MACs.

``dtype`` is the float type of the arithmetic: float64 is the reference,
float32 the control.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

DIMS = ("b", "i", "o")
SPACES = (  # name, dims, is_output
    ("In0", ("b", "i"), False),
    ("In1", ("i", "o"), False),
    ("Out", ("b", "o"), True),
)


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def violations(dims: Dict[str, int], arch: Dict, mapping: Dict, word_bytes: int) -> List[str]:
    """Why ``mapping`` is not a legal mapping of the GEMM (empty if legal)."""
    levels, clusters = mapping["levels"], arch["levels"]
    errs: List[str] = []
    if len(levels) != len(clusters):
        return [f"{len(levels)} mapping levels for {len(clusters)} architecture levels"]
    outer = dict(dims)
    for l, lm in enumerate(levels):
        tt = {d: int(lm["temporal_tile_sizes"].get(d, 1)) for d in DIMS}
        st = {d: int(lm["spatial_tile_sizes"].get(d, 1)) for d in DIMS}
        inner = levels[l + 1]["temporal_tile_sizes"] if l + 1 < len(levels) else None
        for d in DIMS:
            if tt[d] < 1 or st[d] < 1 or outer[d] % tt[d] or tt[d] % st[d]:
                errs.append(f"L{l}:{d} tiles {outer[d]}/{tt[d]}/{st[d]} do not divide")
            if inner is not None:
                itt = int(inner.get(d, 1))
                if st[d] < itt or st[d] % itt:
                    errs.append(f"L{l}:{d} inner tile {itt} does not divide {st[d]}")
        child = clusters[l + 1]["fanout"] if l + 1 < len(clusters) else 1
        if _prod(tt[d] // max(1, st[d]) for d in DIMS) > child:
            errs.append(f"L{l} spreads over more than {child} sub-clusters")
        cap = clusters[l].get("memory_bytes")
        if cap is not None and l > 0:
            need = sum(_prod(tt[d] for d in ds) for _n, ds, _o in SPACES) * word_bytes
            if need > cap:
                errs.append(f"L{l} tiles need {need} B of {cap} B")
        if not set(lm["temporal_order"]) <= set(DIMS):
            errs.append(f"L{l} orders unknown dims")
        if l == len(levels) - 1 and tt != st:
            errs.append("the innermost level cannot spread")
        outer = st
    return errs


def cost(dims: Dict[str, int], arch: Dict, mapping: Dict, word_bytes: int,
         dtype=np.float64) -> Dict[str, float]:
    """Latency (cycles), energy (pJ) and EDP (J s) of a legal mapping."""
    F = dtype
    levels, clusters = mapping["levels"], arch["levels"]
    n = len(levels)
    buffered = [l for l in range(n) if clusters[l].get("memory_bytes") is not None]
    parent: List[Optional[int]] = []
    for l in range(n):
        above = [p for p in buffered if p < l]
        parent.append(above[-1] if above else None)

    tloops: List[Tuple[int, str, int]] = []  # (level, dim, trips), outer first
    sloops: List[Tuple[int, str, int]] = []
    outer = dict(dims)
    tiles = []
    for l, lm in enumerate(levels):
        tt = {d: int(lm["temporal_tile_sizes"].get(d, 1)) for d in DIMS}
        st = {d: int(lm["spatial_tile_sizes"].get(d, 1)) for d in DIMS}
        tiles.append(tt)
        declared = list(lm["temporal_order"])
        for d in declared + [d for d in DIMS if d not in declared]:
            trips = max(1, outer[d] // tt[d])
            if trips > 1:
                tloops.append((l, d, trips))
        for d in DIMS:
            if tt[d] // st[d] > 1:
                sloops.append((l, d, tt[d] // st[d]))
        outer = st

    macs = _prod(dims.values())
    leaf = clusters[-1]
    compute = _prod(t for _l, _d, t in tloops) * math.ceil(
        _prod(tiles[-1].values()) / leaf["macs_per_cycle"]
    )
    instances = [_prod(f for lv, _d, f in sloops if lv < l) for l in range(n)]

    traffic = {}  # (space, level) -> (fills, drains, parent reads, parent writes)
    for name, ds, is_out in SPACES:
        for l in buffered:
            changes = unique = 1
            irrelevant = 1
            for lv, d, t in tloops:
                if lv > l:
                    break
                if d in ds:
                    unique *= t
                    changes = unique * irrelevant
                else:
                    irrelevant *= t
            foot = _prod(tiles[l][d] for d in ds)
            p = parent[l]
            spread = 1 if p is None else _prod(
                f for lv, d, f in sloops if p <= lv < l and d in ds
            )
            if is_out:
                rmw = max(0, changes - unique) * foot
                traffic[name, l] = (rmw, changes * foot, rmw * spread, changes * foot * spread)
            else:
                traffic[name, l] = (changes * foot, 0, changes * foot * spread, 0)

    freq = F(arch["frequency_hz"])
    latency = F(compute)
    for l in buffered:
        bw = clusters[l].get("fill_bandwidth")
        if l == 0 or bw is None:
            continue
        nbytes = sum((traffic[s, l][0] + traffic[s, l][1]) * word_bytes for s, _d, _o in SPACES)
        if nbytes > 0:
            latency = max(latency, F(nbytes) * freq / F(bw))

    energy = F(0)
    for name, _ds, is_out in SPACES:
        for l in buffered:
            fills, drains, preads, pwrites = traffic[name, l]
            cl = clusters[l]
            energy += F(fills * instances[l] * word_bytes) * F(cl["write_energy"])
            energy += F(drains * instances[l] * word_bytes) * F(cl["read_energy"])
            p = parent[l]
            if p is not None:
                pc = clusters[p]
                energy += F(preads * instances[p] * word_bytes) * F(pc["read_energy"])
                energy += F(pwrites * instances[p] * word_bytes) * F(pc["write_energy"])
        energy += F((2 if is_out else 1) * macs * word_bytes) * F(leaf["read_energy"])
    energy += F(macs) * F(leaf["mac_energy"])
    edp = energy * F(1e-12) * (latency / freq)
    return {"latency_cycles": float(latency), "energy_pj": float(energy), "edp": float(edp)}
