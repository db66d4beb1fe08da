"""Batched serving driver: slot-based continuous batching over decode_step.

Requests (token prompts) fill a fixed pool of batch slots; each engine tick
decodes one token for every active slot; finished sequences release their
slot to queued requests.  Prompts enter via teacher-forced decode of their
tokens (prefill-by-decode keeps one compiled program — appropriate at smoke
scale; the prefill-shape dry-run covers the batched-prefill path).
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class _Slot:
    request: Optional[Request] = None
    pos: int = 0
    feed_idx: int = 0   # how much of the prompt is consumed


class ServeEngine:
    def __init__(self, cfg, params, batch_slots: int, max_len: int):
        from ..models import init_decode_state
        from ..runtime.steps import make_serve_step

        self.cfg = cfg
        self.params = params
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.state = init_decode_state(cfg, batch_slots, max_len)
        # pristine single-slot state, written into a slot at admission:
        # recurrent mixers (SSM/xLSTM) carry hidden state across tokens,
        # so a reused slot must not leak its previous occupant's state
        # into the next request (KV slots are safe via position masking,
        # but they are reset too — it is the same write)
        self._fresh_state = init_decode_state(cfg, 1, max_len)
        self.slots = [_Slot() for _ in range(batch_slots)]
        self.queue: List[Request] = []
        # per-slot position vector: a freed slot re-admits at pos=0 while
        # its neighbors keep decoding mid-stream (continuous batching
        # without the old pos=0 admission-alignment restriction)
        self._step = jax.jit(make_serve_step(cfg, per_slot_pos=True),
                             donate_argnums=(1,))

    def submit(self, request: Request) -> None:
        self.queue.append(request)

    def _reset_slot_state(self, idx: int) -> None:
        """Overwrite batch slot `idx` (axis 1 of every (L, B, ...) state
        leaf) with freshly-initialized decode state."""
        self.state = jax.tree.map(
            lambda st, fresh: st.at[:, idx].set(
                fresh[:, 0].astype(st.dtype)),
            self.state, self._fresh_state)

    def _fill_slots(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.request is None and self.queue:
                slot.request = self.queue.pop(0)
                slot.pos = 0
                slot.feed_idx = 0
                self._reset_slot_state(i)

    @property
    def active(self) -> bool:
        return bool(self.queue) or any(s.request for s in self.slots)

    def tick(self) -> None:
        """One engine step: feed prompt token or consume generated token."""
        self._fill_slots()
        tokens = np.zeros((self.batch_slots,), np.int32)
        pos = np.zeros((self.batch_slots,), np.int32)
        for i, slot in enumerate(self.slots):
            pos[i] = slot.pos
            r = slot.request
            if r is None:
                continue
            if slot.feed_idx < len(r.prompt):
                tokens[i] = r.prompt[slot.feed_idx]
            else:
                tokens[i] = r.generated[-1] if r.generated else 0
        next_tok, logits, self.state = self._step(
            self.params, self.state, jnp.asarray(tokens), jnp.asarray(pos))
        next_tok = np.asarray(next_tok)
        for i, slot in enumerate(self.slots):
            r = slot.request
            if r is None:
                continue
            slot.pos += 1
            if slot.feed_idx < len(r.prompt):
                slot.feed_idx += 1
                if slot.feed_idx == len(r.prompt):
                    r.generated.append(int(next_tok[i]))
            else:
                r.generated.append(int(next_tok[i]))
            if len(r.generated) >= r.max_new_tokens or \
                    slot.pos >= self.max_len - 1:
                r.done = True
                slot.request = None

    def run(self) -> None:
        while self.active:
            self.tick()


def main(argv=None) -> Dict[int, List[int]]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    args = ap.parse_args(argv)

    from ..configs import get_config, smoke_config
    from ..models import init_params
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = smoke_config(get_config(args.arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, args.slots, args.max_len)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=list(rng.integers(0, cfg.vocab_size, size=4)),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    out = {r.rid: r.generated for r in reqs}
    for rid, toks in out.items():
        print(f"request {rid}: {len(toks)} tokens: {toks[:8]}...")
    return out


if __name__ == "__main__":
    main()
