"""Mapping-engine driver: a closed loop of single ``union_opt`` queries.

The queries cycle in a seeded order over the GEMMs the program's
``opstream`` lowers the cell's model to, at the mix's shapes. Each is one
``union_opt`` call on the mix's architecture (built from the mix's copy of
its numbers), mapper, cost model and metric, with the jax engine, a mapper
seed drawn from ``--seed`` and the query's index, and no result store.

Set-up traces the engine's fused cores at every batch bucket a query can
pad to, then answers each GEMM once with a seed the window does not use.
``map_queries_per_s`` is the queries answered inside the window over the
window. With ``--trace 1`` the mix's ``trace_queries`` first queries alone
run, under the profiler (a query dispatches hundreds of small programs,
and a whole window's trace would run to hundreds of megabytes).

``correct``: a sample of the window's answers drawn from the seed is
checked after the window: each answer's mapping must be legal and its
cost equal to the plain reference's (``bench/reference/timeloop.py``),
and its cost no worse than the program's host (numpy) search of the same
query finds.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List

from bench import gen, harness
from bench import trace as tr
from bench.reference import timeloop

SEED_MOD = 2**31 - 1


def architecture(a: Dict):
    """The program's Architecture from the mix's copy of its numbers."""
    from repro.core.architecture import Architecture, Cluster

    inf = float("inf")
    return Architecture(
        a["name"],
        [
            Cluster(
                c["name"], c["fanout"], c["dimension"],
                memory_bytes=c.get("memory_bytes"),
                fill_bandwidth=inf if c.get("fill_bandwidth") is None else c["fill_bandwidth"],
                read_energy=c.get("read_energy", 0.0),
                write_energy=c.get("write_energy", 0.0),
                macs_per_cycle=c.get("macs_per_cycle", 0),
                mac_energy=c.get("mac_energy", 0.0),
            )
            for c in a["levels"]
        ],
        frequency_hz=a["frequency_hz"],
        attrs={"word_bytes": a["word_bytes"]},
    )


def gemms(ctx) -> List[Dict]:
    """The mix's GEMMs, each with the Problem the program's ``opstream``
    lowers the model to; the lowering must give the mix's sizes."""
    from repro.core.opstream import build_opstream

    model = ctx.cell.config["model"]
    streams = {}
    out = []
    for g in ctx.cell.traffic["gemms"]:
        if g["shape"] not in streams:
            streams[g["shape"]] = {
                e.problem.name: e.problem
                for e in build_opstream(model, g["shape"]).mappable_entries()
            }
        p = streams[g["shape"]].get(g["problem"])
        dims = {d: g[d] for d in timeloop.DIMS}
        if p is None or dict(p.dims) != dims:
            raise harness.BenchError(f"{g['name']}: the program lowers no GEMM of {dims}")
        out.append({"name": g["name"], "problem": p, "dims": dims})
    return out


def query(q: Dict, arch, mix: Dict, seed: int, backend: str):
    from repro.core.optimizer import union_opt

    return union_opt(
        q["problem"], arch, mapper=mix["mapper"], cost_model=mix["cost_model"],
        metric=mix["metric"], engine_backend=backend, seed=seed, **mix["mapper_kw"],
    )


def run(ctx: harness.RunContext) -> None:
    from repro.core.cost.engine import EvaluationEngine
    from repro.core.optimizer import COST_MODEL_REGISTRY

    mix = ctx.cell.traffic
    arch = architecture(mix["arch"])
    qs = gemms(ctx)
    # set-up: the fused cores at every bucket a query pads to, then each
    # GEMM answered once with a seed outside the window's
    for q in qs[:1]:
        eng = EvaluationEngine(COST_MODEL_REGISTRY[mix["cost_model"]](), q["problem"],
                               arch, metric=mix["metric"], backend="jax")
        eng.warmup(mix["warm_buckets"])
    for q in qs:
        query(q, arch, mix, SEED_MOD, "jax")
    ctx.setup_s = harness.seconds_since(ctx.t_start)

    order = gen.query_order(len(qs), ctx.seed, 100000)
    answers = []
    limit = int(mix["trace_queries"]) if ctx.trace else None
    t_open = time.perf_counter()
    close = t_open + ctx.seconds
    with (tr.Tracer(ctx.work_dir / "trace") if ctx.trace else contextlib.nullcontext()) as tracer:
        k = 0
        while (k < limit) if ctx.trace else (time.perf_counter() < close):
            idx = order[k]
            seed = (ctx.seed * 1000003 + k) % SEED_MOD
            t0 = time.perf_counter()
            with tr.annotate(f"query.{qs[idx]['name']}"):
                sol = query(qs[idx], arch, mix, seed, "jax")
            answers.append({"q": idx, "seed": seed, "sol": sol, "t0": t0, "t1": time.perf_counter()})
            k += 1
    if ctx.trace:
        ctx.trace_reduction = tracer.reduce()
    ctx.attempted = len(answers)
    ctx.failed = sum(1 for a in answers if a["sol"].mapping is None)
    if not ctx.trace:
        in_window = [a for a in answers if a["t1"] <= close]
        ctx.end_to_end["map_queries_per_s"] = len(in_window) / ctx.seconds
    s = [a["sol"].search for a in answers]
    ctx.counters.update(
        queries=len(answers),
        query_s=sum(a["t1"] - a["t0"] for a in answers),
        evaluated=sum(x.evaluated for x in s),
        device_syncs=sum(x.device_syncs for x in s),
        fused_dispatches=sum(x.fused_dispatches for x in s),
        backend_fallbacks=sum(x.backend_fallbacks for x in s),
        n_traces=sum(x.n_traces for x in s),
    )
    ctx.device = harness.device_info(ctx.devices)
    _check(ctx, qs, arch, answers)


def _check(ctx, qs, arch, answers) -> None:
    mix = ctx.cell.traffic
    a_ref = mix["arch"]
    want = int(mix["check"]["queries"])
    picked = [answers[i] for i in gen.sample_indices(len(answers), want, ctx.seed, 0)] if answers else []
    illegal, ref_gap, host_gap = 0, 0.0, 0.0
    for a in picked:
        q = qs[a["q"]]
        sol = a["sol"]
        if sol.mapping is None:
            illegal += 1
            continue
        m = sol.mapping.to_dict()
        dims = q["dims"]
        wb = int(mix["word_bytes"])
        if timeloop.violations(dims, a_ref, m, wb):
            illegal += 1
            continue
        ref = timeloop.cost(dims, a_ref, m, wb)
        ref_gap = max(ref_gap, abs(sol.cost.edp - ref["edp"]) / ref["edp"])
        host = query(q, arch, mix, a["seed"], "numpy")
        host_gap = max(host_gap, (sol.cost.edp - host.cost.edp) / host.cost.edp)
    limits = harness.load_json(ctx.cell.bench_dir / "limits" / f"{ctx.cell.name}.json")
    ctx.counters["checked_queries"] = len(picked)
    ctx.checks += [
        harness.Check("illegal_answers", float(illegal + (0 if picked else 1)), 0.0),
        harness.Check("edp_gap_vs_reference", ref_gap, float(limits["edp_gap_vs_reference"])),
        harness.Check("edp_gap_vs_host_search", host_gap, float(limits["edp_gap_vs_host_search"])),
    ]
