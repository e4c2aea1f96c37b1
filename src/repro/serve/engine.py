"""Single-host serving engine: continuous batching over a SPARTA-paged pool.

The device pool is one array ``[L, P*S, page, Hkv, hd]`` whose slot space is
partition-major (slot = partition * S + local) — the logical "distributed
memory" of the paper collapsed onto one device for the runnable example; the
multi-device layout is exercised by the dry-run / sharded tests.

Features demonstrated end-to-end:
* demand allocation (pages appear as sequences grow),
* prefix sharing via ``fork`` + copy-on-write on the shared tail page,
* continuous batching (requests join/leave the batch between steps),
* prefill via ``prefill_with_kv`` scattered through the block tables.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.paged_kv import FREE, PagedKVConfig, SpartaKVManager
from repro.models import transformer as tfm


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    seq_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    # Top-1 minus top-2 logit of each greedy pick: how close the choice was
    # to a tie (two numerically different backends may only disagree there).
    margins: List[float] = dataclasses.field(default_factory=list)
    done: bool = False


def greedy(logits: jnp.ndarray):
    """Greedy pick over the last axis: (token ids, top-1 minus top-2 logit)."""
    top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
    return jnp.argmax(logits, axis=-1), top2[..., 0] - top2[..., 1]


class SpartaEngine:
    def __init__(self, cfg: ModelConfig, params, *, num_partitions: int = 4,
                 slots_per_partition: int = 64, max_batch: int = 4,
                 kernel_mode: str = "auto"):
        self.cfg = cfg
        self.params = params
        self.kernel_mode = kernel_mode
        self.max_batch = max_batch
        self.kv = SpartaKVManager(PagedKVConfig(
            num_partitions=num_partitions,
            slots_per_partition=slots_per_partition,
            page_size=cfg.kv_page_size,
        ))
        L = cfg.num_layers
        total = num_partitions * slots_per_partition
        shape = (L, total, cfg.kv_page_size, cfg.num_kv_heads, cfg.head_dim)
        self.k_pool = jnp.zeros(shape, jnp.float32)
        self.v_pool = jnp.zeros(shape, jnp.float32)
        self.waiting: List[Request] = []
        self.active: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self._next_rid = 0

        def decode(p, tok, kp, vp, tbl, ctx):
            logits, kp, vp = tfm.decode_step(
                p, tok, cfg, kp, vp, tbl, ctx, kernel_mode=kernel_mode)
            return greedy(logits), kp, vp

        self._decode = jax.jit(decode)

    # -- request API ---------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.waiting.append(Request(rid, list(prompt), max_new_tokens))
        return rid

    def fork_request(self, rid: int, max_new_tokens: int = 16) -> int:
        """Prefix sharing: continue a finished/active request as a new branch
        (beam-search-style) — pages are shared, the tail page copies on
        write."""
        src = self.finished.get(rid) or next(r for r in self.active if r.rid == rid)
        child_sid = self.kv.fork(src.seq_id)
        rid2 = self._next_rid
        self._next_rid += 1
        req = Request(rid2, src.prompt + src.generated, max_new_tokens, seq_id=child_sid)
        self.active.append(req)
        return rid2

    # -- internals ------------------------------------------------------------

    def _global_slot(self, partition: int, local: int) -> int:
        return partition * self.kv.cfg.slots_per_partition + local

    def _prefill(self, req: Request) -> None:
        cfg, page = self.cfg, self.cfg.kv_page_size
        req.seq_id = self.kv.new_sequence()
        events = self.kv.append_tokens(req.seq_id, len(req.prompt))
        tokens = jnp.asarray(np.array(req.prompt, np.int32))[None]
        logits, kpages, vpages = tfm.prefill_with_kv(
            self.params, tokens, cfg, kernel_mode=self.kernel_mode)
        # Scatter the page-layout KV into the pool through the block table.
        for ev in events:
            g = self._global_slot(ev["partition"], ev["slot"])
            self.k_pool = self.k_pool.at[:, g].set(kpages[:, 0, ev["lp"]].astype(self.k_pool.dtype))
            self.v_pool = self.v_pool.at[:, g].set(vpages[:, 0, ev["lp"]].astype(self.v_pool.dtype))
        nxt, margin = greedy(logits[0, -1])
        req.generated.append(int(nxt))
        req.margins.append(float(margin))

    def _apply_events(self, events: List[dict]) -> None:
        """Apply CoW copies (old slot -> new slot, same partition)."""
        for ev in events:
            if ev["kind"] == "cow":
                g_new = self._global_slot(ev["partition"], ev["slot"])
                g_old = self._global_slot(ev["partition"], ev["old_slot"])
                self.k_pool = self.k_pool.at[:, g_new].set(self.k_pool[:, g_old])
                self.v_pool = self.v_pool.at[:, g_new].set(self.v_pool[:, g_old])

    def step(self) -> int:
        """One engine tick: admit, decode one token for every active request,
        retire finished ones.  Returns the number of active requests."""
        while self.waiting and len(self.active) < self.max_batch:
            req = self.waiting.pop(0)
            self._prefill(req)
            self.active.append(req)
        if not self.active:
            return 0

        # Grow each sequence by one token (allocates pages on demand + CoW).
        for req in self.active:
            self._apply_events(self.kv.append_tokens(req.seq_id, 1))

        seqs = [r.seq_id for r in self.active]
        max_pages = max(len(self.kv.seq_pages(s)) for s in seqs)
        table = self.kv.global_block_table(seqs, max_pages)
        ctx = self.kv.context_lengths(seqs)
        last = np.array([ (r.prompt + r.generated)[-1] for r in self.active], np.int32)

        (nxt, margin), self.k_pool, self.v_pool = self._decode(
            self.params, jnp.asarray(last), self.k_pool, self.v_pool,
            jnp.asarray(table), jnp.asarray(ctx),
        )
        nxt, margin = np.asarray(nxt), np.asarray(margin)
        for i, req in enumerate(self.active):
            req.generated.append(int(nxt[i]))
            req.margins.append(float(margin[i]))
            if len(req.generated) >= req.max_new_tokens:
                req.done = True
        for req in [r for r in self.active if r.done]:
            self.active.remove(req)
            self.finished[req.rid] = req
        return len(self.active)

    def run_to_completion(self, max_steps: int = 1000) -> None:
        for _ in range(max_steps):
            if not self.step() and not self.waiting:
                return
