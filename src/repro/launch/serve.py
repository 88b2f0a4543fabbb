"""LLM serving driver: batched continuous-batching engine with backpressure
admission (dummy-slot padding = the paper's regulator made literal — XLA
needs static shapes, so empty slots run as dummy packets and are ignored on
output).

This is the *model-serving* demo over `repro.models`; the paper's
network-computation serving subsystem lives in `repro.serving` (trace ->
admission -> bp_slot -> latency scoring, DESIGN.md §9).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 8 --slots 4 --max-new 12
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import get_model, split_tree


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    """Continuous batching over fixed decode slots with dummy-slot padding.

    Drives any arch through the uniform ModelAPI: submit prompts, `step()`
    prefills newly admitted requests (one at a time, cache-filling decode
    of the prompt) and decodes one token for every active slot.
    """

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 temperature: float = 0.0, seed: int = 0):
        self.cfg = cfg
        self.api = get_model(cfg)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.key = jax.random.key(seed)
        self.caches = self.api.init_decode(slots, max_len, jnp.float32)
        self.router_H = self.api.init_state().router_H
        self.slot_req: List[Optional[ServeRequest]] = [None] * slots
        self.pending: List[ServeRequest] = []
        self.finished: Dict[int, ServeRequest] = {}
        self._last_tok = np.zeros((slots,), np.int32)

        def step_fn(params, caches, tokens, H):
            return self.api.decode_step(params, caches, {"tokens": tokens},
                                        activ_dtype=jnp.float32, router_H=H)
        self._step = jax.jit(step_fn)

    # ------------------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int = 16) -> int:
        rid = len(self.finished) + len(self.pending) + sum(
            r is not None for r in self.slot_req)
        self.pending.append(ServeRequest(rid, list(prompt), max_new))
        return rid

    def _admit(self):
        for s in range(self.slots):
            if self.slot_req[s] is None and self.pending:
                req = self.pending.pop(0)
                self.slot_req[s] = req
                # prefill by decoding the prompt into this slot's cache:
                # tokens of OTHER slots are dummy packets (last token echo).
                for tok in req.prompt[:-1]:
                    toks = self._last_tok.copy()
                    toks[s] = tok
                    _, self.caches = self._step(self.params, self.caches,
                                                jnp.asarray(toks),
                                                self.router_H)
                    # A copy: the step may still read `toks` (jnp.asarray
                    # aliases host memory on the CPU), and _last_tok is
                    # written below.
                    self._last_tok = toks.copy()
                self._last_tok[s] = req.prompt[-1]

    def step(self) -> int:
        """One decode tick over all slots; returns #active real slots."""
        self._admit()
        active = [s for s in range(self.slots) if self.slot_req[s] is not None]
        logits, self.caches = self._step(self.params, self.caches,
                                         jnp.asarray(self._last_tok),
                                         self.router_H)
        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            nxt = jax.random.categorical(sub, logits / self.temperature, -1)
        else:
            nxt = jnp.argmax(logits, -1)
        nxt = np.asarray(nxt, np.int32)
        for s in active:
            req = self.slot_req[s]
            req.out.append(int(nxt[s]))
            self._last_tok[s] = nxt[s]
            if len(req.out) >= req.max_new:
                req.done = True
                self.finished[req.rid] = req
                self.slot_req[s] = None
        return len(active)

    def run_until_done(self, max_ticks: int = 10_000) -> Dict[int, ServeRequest]:
        for _ in range(max_ticks):
            if not self.pending and all(r is None for r in self.slot_req):
                break
            self.step()
        return self.finished


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    api = get_model(cfg)
    params, _ = split_tree(api.init(key=jax.random.key(args.seed)))
    eng = Engine(cfg, params, slots=args.slots, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        plen = int(rng.integers(4, 16))
        eng.submit(list(rng.integers(0, cfg.vocab, plen)), args.max_new)

    t0 = time.time()
    finished = eng.run_until_done()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in finished.values())
    print(f"served {len(finished)} requests, {toks} tokens "
          f"in {dt:.1f}s ({toks/dt:.1f} tok/s on CPU)")
    for rid in sorted(finished)[:4]:
        print(f"  req {rid}: out={finished[rid].out[:8]}...")
    assert len(finished) == args.requests
    return finished


if __name__ == "__main__":
    main()
