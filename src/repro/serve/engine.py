"""Batched serving engine: continuous batching over a fixed-slot KV cache.

The production pattern (vLLM-style, sized down): a fixed decode batch of
``slots``, each slot holding one request's KV/SSM state at a fixed
``max_seq`` budget.  Requests queue up; whenever a slot frees (EOS or
length budget), the next request is prefilled into that slot and decoding
continues for the whole batch every step.  Per-slot position/length
bookkeeping lives on the host; the device step is one jitted
``decode_step`` over the full slot batch (slots beyond their length emit
garbage that is masked on the host — the standard padding-decode trade).

Single-slot prefill uses a per-request jitted prefill over a length-
bucketed prompt (bucketing avoids a compile per prompt length).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model
from repro.models.config import LOCAL, ModelConfig, ShardCfg
from repro.sim.slots import SlotTable


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int = 32
    eos_id: int | None = None
    # filled by the engine
    output: list = dataclasses.field(default_factory=list)
    done: bool = False


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_seq: int = 512, shard: ShardCfg = LOCAL,
                 greedy: bool = True):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.shard = shard
        self.table = SlotTable(slots)
        self.finished: list[Request] = []
        self.lengths = np.zeros((slots,), np.int32)   # filled tokens per slot
        self.budgets = np.zeros((slots,), np.int32)
        self.caches = model.init_caches(cfg, slots, max_seq, jnp.float32)
        self.last_token = np.zeros((slots, 1), np.int32)
        self.steps = 0
        # exact per-leaf batch axis: the axis whose extent tracks the batch
        a = jax.eval_shape(lambda: model.init_caches(cfg, slots, max_seq,
                                                     jnp.float32))
        b = jax.eval_shape(lambda: model.init_caches(cfg, slots + 1, max_seq,
                                                     jnp.float32))
        self._batch_axes = jax.tree.map(
            lambda x, y: int(next(i for i, (u, v) in
                                  enumerate(zip(x.shape, y.shape)) if u != v)),
            a, b)

        self._decode = jax.jit(
            lambda p, t, c, l: model.decode_step(p, cfg, t, c, l, shard))
        self._prefill_cache = {}

    # -- request intake ---------------------------------------------------------
    def submit(self, req: Request):
        self.table.submit(req)

    @property
    def active(self) -> list[Request | None]:
        return [self.table.get(s) for s in range(self.slots)]

    def _prefill_fn(self, plen: int):
        if plen not in self._prefill_cache:
            def fn(p, tokens, caches):
                # single-request prefill into slot-0 of a 1-batch cache view
                return model.prefill(p, self.cfg, {"tokens": tokens}, caches,
                                     self.shard)

            self._prefill_cache[plen] = jax.jit(fn)
        return self._prefill_cache[plen]

    def _admit(self):
        while True:
            admitted = self.table.admit_next()
            if admitted is None:
                return
            s, req = admitted
            plen = len(req.prompt)
            b = _bucket(plen)
            toks = np.full((1, b), 0, np.int32)
            toks[0, :plen] = req.prompt
            toks = jnp.asarray(toks)
            one_cache = model.init_caches(self.cfg, 1, self.max_seq,
                                          jnp.float32)
            logits, one_cache = self._prefill_fn(b)(self.params, toks,
                                                    one_cache)
            # bucketing pads the prompt; recompute last real-token logits by
            # decoding nothing — we take argmax at position plen-1 via the
            # cache, i.e. accept one wasted pad region (documented trade)
            self.caches = jax.tree.map(
                lambda full, one, ax: jax.lax.dynamic_update_index_in_dim(
                    full, jnp.take(one, 0, axis=ax), s, ax),
                self.caches, one_cache, self._batch_axes)
            # re-decode the last real prompt token: its KV rewrite at
            # position plen-1 is idempotent and yields the first new token
            # without a per-length prefill compile (bucketed pads beyond
            # plen are masked by the per-slot valid length)
            self.lengths[s] = plen - 1
            self.budgets[s] = req.max_new_tokens
            self.last_token[s, 0] = int(req.prompt[-1])

    # -- one engine step -------------------------------------------------------
    def step(self):
        self._admit()
        if self.table.n_active == 0:
            return False
        cache_len = jnp.asarray(self.lengths)        # (slots,) per-slot fill
        logits, self.caches = self._decode(
            self.params, jnp.asarray(self.last_token), self.caches, cache_len)
        toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        self.steps += 1
        for s, req in list(self.table.occupied()):
            t = int(toks[s])
            req.output.append(t)
            self.last_token[s, 0] = t
            self.lengths[s] += 1
            self.budgets[s] -= 1
            if ((req.eos_id is not None and t == req.eos_id)
                    or self.budgets[s] <= 0
                    or self.lengths[s] >= self.max_seq - 1):
                req.done = True
                self.finished.append(req)
                self.table.release(s)
                self.lengths[s] = 0
        return True

    def run_until_drained(self, max_steps: int = 10_000):
        while self.steps < max_steps:
            if not self.step():
                if self.table.idle:
                    break
        return self.finished


