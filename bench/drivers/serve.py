"""Serving driver: a closed loop of clients against ``launch.serve.WaveServer``.

Set-up makes the weights from the seed, builds the server and serves one
short wave at the cell's own slot count and cache length, which compiles
every program the window runs. Each client's next request is submitted
when its last one completes, and waits in the queue until the next wave.

The window opens when the first measured wave is submitted and takes whole
waves until it has lasted ``--seconds``: the server hands out tokens in
bursts (none while a wave prefills), so a window that cut a wave would
count a burst or not by a hair's breadth of timing. Each token is timed
where the server hands it to the request: the request's ``out`` list
stamps every append. ``serve_tokens_per_s`` is the tokens handed in the
window over its length; ``ttft_p90_s`` the 90th percentile, over every
request the window's waves serve, of submission to first token.

With ``--trace 1`` the first measured wave alone is served, under the
profiler, and the per-layer metrics are read from it.

``correct``: a sample of finished requests, drawn from the seed and with
the longest in it, goes through the plain float32 reference
(``bench/reference/dense.py``) over the same left-padded prompt and the
served tokens; the widest gap by which a served token's logit lies below
the reference's best is held to the cell's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench import gen, harness
from bench import trace as tr
from bench.reference import dense
from bench.weights import count_params, make_params

SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
             "vocab", "qk_norm", "qkv_bias", "tie_embeddings", "rope_theta",
             "rms_eps", "act")


def model_config(c: Dict):
    from repro.configs.base import get_config

    return dataclasses.replace(get_config(c["model"]), **{k: c[k] for k in SIZE_KEYS})


class Stamped(list):
    """A request's ``out`` list that stamps each token as it is handed over."""

    def __init__(self) -> None:
        super().__init__()
        self.times: List[float] = []

    def append(self, tok) -> None:
        super().append(tok)
        self.times.append(time.perf_counter())


@dataclasses.dataclass
class Rec:
    client: int
    submit: float
    prompt: List[int]
    max_new: int
    wave_len: int  # the wave's longest prompt: the left-padded length
    out: Stamped

    @property
    def first(self):
        return self.out.times[0] if self.out.times else None

    @property
    def done_at(self):
        return self.out.times[self.max_new - 1] if len(self.out) >= self.max_new else None


class Loop:
    """The closed loop: one request per client in each wave."""

    def __init__(self, server, Request, gen_loop, slots: int) -> None:
        self.server, self.Request, self.gen = server, Request, gen_loop
        self.slots = slots
        self.recs: List[Rec] = []
        self.wave_times: List[Dict[str, float]] = []
        self.ready: List[float] = []  # when each client sent its next request

    def serve_wave(self) -> None:
        w = len(self.wave_times)
        t0 = time.perf_counter()
        if not self.ready:
            self.ready = [t0] * self.slots
        reqs = self.gen.wave(w)
        wave_len = max(len(g.prompt) for g in reqs)
        recs = []
        for g in reqs:
            rec = Rec(g.client, self.ready[g.client], g.prompt, g.max_new, wave_len, Stamped())
            recs.append(rec)
            self.server.submit(self.Request(len(self.recs) + len(recs), g.prompt, g.max_new,
                                            out=rec.out))
        self.server.run()
        self.recs += recs
        self.wave_times.append({
            "start": t0,
            "first": min(r.first for r in recs),
            "end": time.perf_counter(),
        })
        for r in recs:  # a request cut by the cache length is done when its wave is
            self.ready[r.client] = r.done_at or self.wave_times[-1]["end"]


def run(ctx: harness.RunContext) -> None:
    import jax

    from repro import kernels
    from repro.codesign import planner_stats, reset_planner_stats
    from repro.launch.serve import Request, WaveServer

    cell, mix = ctx.cell, ctx.cell.traffic
    cfg = model_config(cell.config)
    slots, max_len = int(mix["slots"]), int(mix["max_len"])
    if int(mix["clients"]) != slots:
        raise harness.BenchError("a closed loop of waves needs one client per slot")
    kernels.enable_pallas(True, interpret=ctx.interpret)
    reset_planner_stats()
    params = make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    ctx.counters["params"] = count_params(params)
    server = WaveServer(cfg, params, batch_slots=slots, max_len=max_len,
                        pad_token=int(mix["pad_token"]))
    # warm-up: one wave at the cell's slot count and cache length compiles
    # the decode step and the cache's initializer, the only programs run
    for c in range(slots):
        server.submit(Request(-1 - c, [int(mix["token_min"])] * 2, 2))
    server.run()
    ctx.counters["planner"] = dict(planner_stats())

    loop = Loop(server, Request, gen.ClosedLoop(mix, cfg.vocab, ctx.seed), slots)
    ctx.setup_s = harness.seconds_since(ctx.t_start)
    if ctx.trace:
        with tr.Tracer(ctx.work_dir / "trace") as tracer:
            loop.serve_wave()
        ctx.trace_reduction = tracer.reduce()
        _traced_wave(ctx, loop)
    else:
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < ctx.seconds:
            loop.serve_wave()
        _end_to_end(ctx, loop)
    ctx.device = harness.device_info(ctx.devices)
    del server
    _check(ctx, params, loop)


def _traced_wave(ctx, loop: Loop) -> None:
    wt, recs = loop.wave_times[0], loop.recs
    ctx.attempted = len(recs)
    ctx.failed = sum(1 for r in recs if r.done_at is None)
    ctx.counters.update(
        wave_s=wt["end"] - wt["start"],
        prefill_s=wt["first"] - wt["start"],
        wave_len=recs[0].wave_len,
        prompt_lens=[len(r.prompt) for r in recs],
        served=[len(r.out) for r in recs],
        slots=loop.slots,
    )


def _end_to_end(ctx, loop: Loop) -> None:
    window = loop.wave_times[-1]["end"] - loop.wave_times[0]["start"]
    ttft = [r.first - r.submit for r in loop.recs]
    tokens = sum(len(r.out) for r in loop.recs)
    ctx.attempted = len(loop.recs)
    ctx.failed = sum(1 for r in loop.recs if r.done_at is None)
    ctx.end_to_end["serve_tokens_per_s"] = tokens / window
    ctx.end_to_end["ttft_p90_s"] = float(np.percentile(ttft, 90))
    ctx.counters.update(window_s=window, window_tokens=tokens, waves=len(loop.wave_times),
                        wave_s=[w["end"] - w["start"] for w in loop.wave_times],
                        prefill_s=[w["first"] - w["start"] for w in loop.wave_times])


def sample_finished(ctx, loop: Loop) -> List[Rec]:
    """Finished requests drawn from the seed, the longest first, until the
    mix's count of served tokens is reached."""
    done = [r for r in loop.recs if r.done_at is not None]
    if not done:
        return []
    want = ctx.cell.traffic["check"]
    longest = max(range(len(done)), key=lambda i: (len(done[i].out), len(done[i].prompt)))
    order = gen.sample_indices(len(done), len(done), ctx.seed, longest)
    picked, served = [], 0
    for i in order:
        if served >= int(want["served_tokens"]) or len(picked) >= int(want["max_requests"]):
            break
        picked.append(done[i])
        served += len(done[i].out)
    return picked


def reference_inputs(rec: Rec, pad: int, max_len: int):
    """The left-padded sequence the server ran, and the served targets."""
    lead = [pad] * (rec.wave_len - len(rec.prompt))
    seq = lead + list(rec.prompt) + list(rec.out[:-1])
    tokens = np.zeros(max_len, np.int32)
    tokens[: len(seq)] = seq
    targets = np.full(max_len, -1, np.int32)
    start = rec.wave_len - 1
    targets[start : start + len(rec.out)] = list(rec.out)
    return tokens, targets


def _check(ctx, params, loop: Loop) -> None:
    mix = ctx.cell.traffic
    gc.collect()  # the server's cache goes before the reference runs
    picked = sample_finished(ctx, loop)
    gaps = []
    for rec in picked:
        tokens, targets = reference_inputs(rec, int(mix["pad_token"]), int(mix["max_len"]))
        g, _ = dense.served_gaps(ctx.cell.config, params, tokens, targets)
        gaps.append(float(np.max(g[targets >= 0])))
    limits = harness.load_json(ctx.cell.bench_dir / "limits" / f"{ctx.cell.name}.json")
    ctx.counters["checked_requests"] = len(picked)
    ctx.counters["checked_tokens"] = sum(len(r.out) for r in picked)
    ctx.checks.append(harness.Check(
        "served_logit_gap", max(gaps) if gaps else float("inf"),
        float(limits["served_logit_gap"]),
    ))
