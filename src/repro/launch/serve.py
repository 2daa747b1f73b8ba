"""Batched serving driver: wave-batched prefill + lock-step decode.

Scheduling model: requests are packed into *waves* of up to ``--batch``
sequences. Prompts in a wave are LEFT-padded to the wave's max prompt
length so every slot shares one scalar cache position (the padding lives
at positions every real token can already attend to, and contributes only
through the softmax over the pad prefix -- it is masked by feeding a
shared pad token and offsetting positions; see ``_prefill``). The wave
then decodes in lock-step; a wave retires when all its members finish.

This is the fixed-shape JAX analogue of batch-of-requests serving; the
decode step is EXACTLY the step the multi-pod dry-run compiles
(launch/steps.make_serve_step). A continuous-batching scheduler with
per-slot position vectors is a server-side extension that changes only
this file, not the model/step layer.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch import steps as steps_mod
from repro.models import init_cache, init_params
from repro.runtime import use_compile_cache


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


class WaveServer:
    """Fixed-shape wave batching on top of make_serve_step."""

    def __init__(self, cfg, params, *, batch_slots: int, max_len: int,
                 pad_token: int = 0) -> None:
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.pad = pad_token
        self.queue: List[Request] = []
        self._decode = jax.jit(steps_mod.make_serve_step(cfg))

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    # ------------------------------------------------------------------ #
    def _prefill(self, wave: List[Request]):
        """Feed left-padded prompts token-by-token through the decode step.

        Left-padding means pad tokens occupy the OLDEST cache positions;
        every sequence's real tokens are contiguous at the end, so the
        shared scalar position is exact. Pad-prefix keys do enter the
        softmax -- acceptable for a pad/BOS token by construction (the
        model treats it as a BOS prefix), and identical across the batch.
        """
        L = max(len(r.prompt) for r in wave)
        toks = np.full((self.slots, L), self.pad, np.int32)
        for i, r in enumerate(wave):
            toks[i, L - len(r.prompt):] = r.prompt
        cache = init_cache(self.cfg, self.slots, self.max_len)
        logits = None
        for t in range(L):
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(toks[:, t : t + 1]), jnp.int32(t)
            )
        return logits, cache, L

    def run_wave(self, wave: List[Request]) -> int:
        """Prefill + decode one wave to completion. Returns decode steps."""
        nxt, cache, pos = self._prefill(wave)
        last = np.asarray(nxt)[:, 0].astype(np.int32)  # (slots,)
        steps = 0
        live = {i: r for i, r in enumerate(wave)}
        for i, r in live.items():
            r.out.append(int(last[i]))
        while any(not r.done for r in wave) and pos < self.max_len - 1:
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(last)[:, None], jnp.int32(pos)
            )
            last = np.asarray(logits)[:, 0].astype(np.int32)
            pos += 1
            steps += 1
            for i, r in list(live.items()):
                if r.done:
                    continue
                r.out.append(int(last[i]))
                if len(r.out) >= r.max_new:
                    r.done = True
                    del live[i]
        for r in wave:
            r.done = True
        return steps

    def run(self) -> List[Request]:
        finished: List[Request] = []
        while self.queue:
            wave = self.queue[: self.slots]
            self.queue = self.queue[self.slots:]
            # pad the wave to full slot count with dummy requests
            while len(wave) < self.slots:
                wave.append(Request(-1, [self.pad], 1))
            self.run_wave(wave)
            finished += [r for r in wave if r.rid >= 0]
        return finished


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b_smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    assert cfg.supports_decode, f"{cfg.name} is encoder-only; nothing to serve"
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    server = WaveServer(cfg, params, batch_slots=args.batch, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=int(rng.integers(4, 12))).tolist()
        server.submit(Request(rid, prompt, args.max_new))

    t0 = time.time()
    done = server.run()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    return {
        "requests": len(done),
        "tokens": toks,
        "tok_per_s": toks / max(dt, 1e-9),
    }


if __name__ == "__main__":
    out = main()
    print(f"served {out['requests']} requests, {out['tokens']} tokens "
          f"({out['tok_per_s']:.1f} tok/s)")
