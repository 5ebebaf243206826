"""Prefill/decode disaggregation: two fleets, one paged-KV handoff.

Serving a request has two phases with opposite hardware appetites:
prefill is one big compute-bound matmul over the whole prompt, decode
is thousands of tiny bandwidth-bound steps.  Colocating them forces
one fleet to straddle both rooflines; disaggregating them lets each
fleet run its phase at its own batch shape — and, for this repo's
purpose, lets each fleet sit behind its *own* ``PowerDomain`` stack so
the prefill-vs-decode energy split is measured per boundary channel
rather than modeled (``DisaggregatedSUT`` in ``repro.harness.sut``).

The handoff rides the paged KV layout: a ``PrefillWorker`` computes
the prompt's K/V as page-shaped blocks ``(L, NB, page, kvh, dh)`` plus
the first output token, and the decode engine scatters those blocks
into freshly allocated physical pages of its own pool
(``ContinuousBatchingEngine._install_slot`` — a prefill-into-slot
minus the compute).  Because K/V is stored post-RoPE at absolute
positions, installed pages are bit-identical to what a local prefill
would have written, so disaggregated decode is token-identical to the
colocated engine.

Flow::

    arrivals -> [PrefillWorker x P] --KVHandoff--> [decode engine, B slots]
                 (compute prompt KV,  (queue)       (install pages, decode
                  emit first token)                  to completion)
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import sharding_ctx
from repro.serving.engine import Request
from repro.serving.kv_pages import GARBAGE_PAGE, PoolExhausted


@dataclasses.dataclass
class KVHandoff:
    """One prefilled request in flight between the fleets.

    Args:
        request: the ``Request``, with ``prefill_start_s`` /
            ``first_token_s`` already stamped (seconds on the serve
            clock) and ``output`` seeded with the first token.
        blocks: per-layer K/V tree, leaves ``(L, NB, page, kvh, dh)``
            — the prompt's cache as page-shaped blocks.
        tok0: the first sampled token (host int) — the decode slot's
            seed token.
        n_tokens: prompt length in tokens (NB = ceil(n_tokens/page)).
    """

    request: Request
    blocks: Any
    tok0: int
    n_tokens: int


class PrefillWorker:
    """One prefill replica: prompt -> page-shaped K/V blocks + token.

    Args:
        model: the target LM (same config as the decode fleet's).
        params: its weights.
        page_size: the decode fleet's KV page size in tokens — block
            boundaries must agree on both sides of the handoff.

    ``prefill(request, t0_s, now)`` returns a ``KVHandoff`` and stamps
    the request's ``prefill_start_s``/``first_token_s`` relative to
    ``t0_s`` (seconds, the shared serve clock).
    """

    def __init__(self, model, params, *, page_size: int, rules=None):
        if page_size <= 0:
            raise ValueError("PrefillWorker needs page_size > 0")
        self.model = model
        self.params = params
        self.page_size = int(page_size)
        self.rules = rules
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("n_blocks",))
        self.prefill_tokens = 0        # host accounting, reset externally

    def _prefill_impl(self, params, tokens, *, n_blocks: int):
        """tokens (1, S) -> (blocks tree (L, NB, page, kvh, dh), tok0).

        The contiguous prefill runs with ``max_len = NB * page`` so the
        cache rows slice cleanly into page-shaped blocks; rows past the
        prompt are zero and are overwritten by the decode fleet's own
        writes at positions ``S..`` (same as a local paged prefill).
        """
        ps = self.page_size
        with sharding_ctx(self.rules):
            logits, cache = self.model.prefill(
                params, {"tokens": tokens}, max_len=n_blocks * ps)
        tok0 = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)

        def to_blocks(leaf):
            # (L, 1, NB*page, ...) -> (L, NB, page, ...)
            lead, tail = leaf.shape[0], leaf.shape[3:]
            return leaf[:, 0].reshape((lead, n_blocks, ps) + tail)

        return jax.tree.map(to_blocks, cache["layers"]), tok0

    def prefill(self, r: Request, t0_s: float,
                now: Callable[[], float] = time.monotonic) -> KVHandoff:
        """Prefill ``r`` on this worker and return the page-granular
        ``KVHandoff`` (KV blocks + the argmax first token — the TTFT
        stamp happens here, on the prefill fleet's clock)."""
        toks = np.asarray(r.prompt).reshape(-1)
        s = int(toks.shape[0])
        n_blocks = -(-s // self.page_size)
        r.prefill_start_s = now() - t0_s
        blocks, tok0 = self._prefill(
            self.params, jnp.asarray(toks, jnp.int32)[None],
            n_blocks=n_blocks)
        tok0 = int(tok0)               # blocks -> true TTFT
        r.first_token_s = now() - t0_s
        r.output = [tok0][: r.max_new_tokens]
        r.prefill_tokens += s
        self.prefill_tokens += s
        return KVHandoff(request=r, blocks=blocks, tok0=tok0, n_tokens=s)


class DisaggregatedEngine:
    """Prefill replicas feeding a decode engine via paged KV handoff.

    Args:
        prefill_workers: one or more ``PrefillWorker`` (same model and
            ``page_size`` as the decode engine).
        decode_engine: a paged, non-speculative
            ``ContinuousBatchingEngine`` (or its sharded subclass) —
            its pool receives the handed-off blocks.

    ``serve(requests, ...)`` has the same contract as
    ``ContinuousBatchingEngine.serve``: honors ``arrival_s``, stamps
    ``first_token_s``/``done_s`` on one t=0 clock, returns completed
    requests.  Prefill runs in one thread per worker (round-robin
    shares); the calling thread runs decode.  Output is
    token-identical to the colocated engine.
    """

    def __init__(self, prefill_workers: list, decode_engine):
        if not prefill_workers:
            raise ValueError("DisaggregatedEngine needs >= 1 "
                             "prefill worker")
        if not getattr(decode_engine, "paged", False):
            raise ValueError("decode engine must be paged "
                             "(kv_page_size > 0) to install handoffs")
        if getattr(decode_engine, "speculative", False):
            raise ValueError("disaggregated decode does not run "
                             "speculatively (the draft never saw the "
                             "prompt)")
        for w in prefill_workers:
            if w.page_size != decode_engine.page_size:
                raise ValueError(
                    f"prefill page_size {w.page_size} != decode "
                    f"page_size {decode_engine.page_size}")
            if (w.model.cfg.n_kv_heads
                    != decode_engine.model.cfg.n_kv_heads):
                raise ValueError(
                    f"prefill n_kv_heads {w.model.cfg.n_kv_heads} != "
                    f"decode n_kv_heads "
                    f"{decode_engine.model.cfg.n_kv_heads} — build "
                    f"workers from the decode engine's model/params "
                    f"(a sharded fleet may have replicated KV heads; "
                    f"see replicate_kv_heads)")
        self.workers = prefill_workers
        self.engine = decode_engine

    def _prefill_share(self, worker: PrefillWorker, share: list,
                       out: "queue.Queue", t0: float,
                       now: Callable[[], float],
                       sleep: Callable[[float], None],
                       honor_arrivals: bool) -> None:
        """Drain this worker's share, SLO-aware: among the requests
        that have already arrived, prefill the highest-priority
        (earliest-arrived within a class) first — an interactive
        short never queues behind a best-effort long that arrived
        moments earlier.  An in-flight prefill is not preempted."""
        backlog = collections.deque(share)     # arrival-sorted
        while backlog:
            if honor_arrivals:
                dt = backlog[0].arrival_s - (now() - t0)
                if dt > 0:
                    sleep(dt)
                t = now() - t0
                arrived = [r for r in backlog if r.arrival_s <= t]
            else:
                arrived = list(backlog)
            r = max(arrived or [backlog[0]],
                    key=lambda q: (q.priority, -q.arrival_s, -q.rid))
            backlog.remove(r)
            out.put(worker.prefill(r, t0, now))

    def serve(self, requests: list[Request],
              now: Callable[[], float] = time.monotonic,
              sleep: Callable[[float], None] = time.sleep,
              honor_arrivals: bool = True) -> list[Request]:
        """Round-robin the requests over the prefill fleet (each worker
        drains its share priority-first), feed the handoffs to the
        decode engine as resumable admissions, and return the completed
        records — same contract as ``ContinuousBatchingEngine.serve``."""
        eng = self.engine
        eng.reset()
        eng.prefix_stats = eng._zero_prefix_stats()
        eng.sched_stats = eng._zero_sched_stats()
        for w in self.workers:
            w.prefill_tokens = 0
        t0 = now()
        ordered = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        rids = [r.rid for r in ordered]
        if len(set(rids)) != len(rids):
            dup = sorted({r for r in rids if rids.count(r) > 1})
            raise ValueError(f"duplicate request ids in admission "
                             f"queue: {dup}")
        handoffs: queue.Queue = queue.Queue()
        threads = [
            threading.Thread(
                target=self._prefill_share,
                args=(w, ordered[i::len(self.workers)], handoffs, t0,
                      now, sleep, honor_arrivals),
                daemon=True)
            for i, w in enumerate(self.workers)]
        for th in threads:
            th.start()

        slots: list[Optional[Request]] = [None] * eng.n_slots
        slot_left = [0] * eng.n_slots
        waiting: list[KVHandoff] = []  # handed off, awaiting a slot
        done: list[Request] = []
        n_expected = len(ordered)
        while len(done) < n_expected:
            # drain the handoff queue without blocking decode; if no
            # slot is busy, block for the next prefilled prompt
            busy = any(s is not None for s in slots)
            try:
                block = (not busy and not waiting
                         and len(done) + sum(s is not None
                                             for s in slots) < n_expected)
                while True:
                    waiting.append(handoffs.get(block=block,
                                                timeout=None))
                    block = False
            except queue.Empty:
                pass
            # install waiting handoffs into free slots
            for b in range(eng.n_slots):
                if slots[b] is not None or not waiting:
                    continue
                h = waiting[0]
                if not self._install(h, b, slots, slot_left, done,
                                     now, t0):
                    if not any(s is not None for s in slots):
                        raise RuntimeError(
                            f"request {h.request.rid} needs more KV "
                            f"pages than the decode pool can ever "
                            f"free ({eng.page_pool.n_pages - 1} "
                            f"usable pages)")
                    break                  # wait for a retiring slot
                waiting.pop(0)
            if not any(s is not None for s in slots):
                continue
            eng.state, buf = eng._decode_chunk(eng.params, eng.state)
            buf_np = np.asarray(jax.device_get(buf))
            eng.sched_stats["decode_chunks"] += 1
            t_chunk = now() - t0
            for b in range(eng.n_slots):
                r = slots[b]
                if r is None:
                    continue
                toks = [int(x) for x in buf_np[b]]
                take = min(slot_left[b], len(toks))
                r.output.extend(toks[:take])
                slot_left[b] -= take
                if slot_left[b] == 0:
                    r.done_s = t_chunk
                    done.append(r)
                    slots[b] = None
                    eng._release_slot(b)
        for th in threads:
            th.join()
        return done

    def _install(self, h: KVHandoff, b: int, slots, slot_left, done,
                 now, t0) -> bool:
        """Scatter a handoff's blocks into slot ``b``'s fresh pages;
        ``False`` defers it (pool pressure — a retiring slot will free
        pages; prefix-cache-only pages are evicted by ``_alloc_pages``)."""
        eng = self.engine
        r = h.request
        s = h.n_tokens
        budget = r.max_new_tokens
        assert s + budget <= eng.max_len, (s, budget, eng.max_len)
        ps = eng.page_size
        nb = -(-s // ps)
        total = min(eng.pages_per_slot, -(-(s + budget) // ps))
        try:
            row = eng._alloc_pages(total)
        except PoolExhausted:
            return False
        eng._slot_pages[b] = list(row)
        eng._slot_toks[b] = tuple(int(x) for x in
                                  np.asarray(r.prompt).reshape(-1))
        eng._slot_base[b] = 0
        row_arr = jnp.asarray(
            row + [GARBAGE_PAGE] * (eng.pages_per_slot - len(row)),
            jnp.int32)
        eng.state = eng._install_slot(
            eng.state, h.blocks, jnp.asarray(h.tok0, jnp.int32),
            jnp.asarray(b, jnp.int32), jnp.asarray(row[:nb], jnp.int32),
            row_arr, jnp.asarray(s, jnp.int32),
            jnp.asarray(budget, jnp.int32))
        if budget <= 1:
            r.done_s = now() - t0
            done.append(r)
            eng._release_slot(b)
        else:
            slots[b] = r
            slot_left[b] = budget - 1
        return True
