"""Serving engines: fixed-batch and slot-based continuous batching.

Two engines share the ``Request`` contract:

``ServeEngine`` (fixed batch)
    Services one batch synchronously: every request prefills together,
    then the whole batch decodes in lock-step for ``max(max_new_tokens)``
    steps, round-tripping each token through the host.  Simple, but the
    batch blocks on its longest request and pays one device->host sync
    per token.

``ContinuousBatchingEngine`` (slot-based, the Server-scenario hot path)
    A persistent decode batch of ``n_slots`` rows backed by a
    preallocated KV cache with a per-slot position vector.  Finished
    slots are retired and refilled from an admission queue *mid-flight*
    (a batch-1 prefill scattered into the slot's cache rows) instead of
    blocking on stragglers.  Decoding runs ``chunk_steps`` tokens fully
    on device (``lax.fori_loop`` + greedy argmax + per-slot done flags),
    so the host syncs once per chunk instead of once per token.

On the production mesh the cache is sequence-sharded over the model
axis (distributed flash-decoding); on CPU the same code runs unsharded.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.parallel.sharding import ShardingRules, sharding_ctx, tp_ctx
from repro.serving.kv_pages import GARBAGE_PAGE, PagePool, PoolExhausted
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculative import greedy_verify, speculative_sample


def _bounded_while(n_steps: int, live, body, init):
    """``fori_loop(0, n_steps, body, init)`` that additionally stops as
    soon as ``live(state)`` is False — chunk loops exit early once every
    slot has exhausted its budget instead of burning whole-batch
    forwards on an inactive batch (budget/chunk misalignment, drain
    tails)."""

    def cond(c):
        i, st = c
        return (i < n_steps) & live(st)

    def step(c):
        i, st = c
        return i + 1, body(i, st)

    return jax.lax.while_loop(cond, step, (0, init))[1]


def _scatter_pages(pool, blocks, pages):
    """Write page-shaped blocks (L, NB, page_size, KVH, D) at physical
    ``pages`` (NB,) of a stacked pool (L, KVH, P, page_size, D): the
    small blocks are transposed to the pool's KV-head-major layout, the
    pool is written in place."""
    return pool.at[:, :, pages].set(
        blocks.transpose(0, 3, 1, 2, 4).astype(pool.dtype))


@dataclasses.dataclass
class Request:
    """One serving request: the contract every engine fills in.

    Caller-set: ``rid`` (unique per serve), ``prompt`` ((S,) int32),
    ``max_new_tokens``, ``arrival_s`` (seconds on the serve clock),
    and the SLO-aware fields ``priority`` (higher = more urgent) and
    ``deadline_s`` (absolute completion deadline on the serve clock;
    ``None`` = best-effort).  Everything else is engine-stamped.
    """

    rid: int
    prompt: Any                       # (S,) int32
    max_new_tokens: int = 16
    arrival_s: float = 0.0
    priority: int = 0                 # scheduler class (higher first)
    deadline_s: Optional[float] = None   # completion deadline, serve
                                         # clock (None = best effort)
    # filled by the engine:
    admitted_s: Optional[float] = None   # first taken from the ready
                                         # queue into a slot (serve clock;
                                         # kept across a park and resume)
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None
    output: Optional[list] = None
    energy_j: Optional[float] = None  # filled by attribute_request_energy
    preemptions: int = 0              # times this request was parked
                                      # (pages evicted, state host-side)
    prefill_start_s: Optional[float] = None  # when prefill compute began
                                      # (disaggregation: on the prefill
                                      # fleet; None = admitted directly)
    draft_tokens: int = 0             # draft-model forwards this request
                                      # triggered (speculative mode)
    verify_tokens: int = 0            # target-model token-forwards this
                                      # request triggered (speculative
                                      # mode: prefill + rounds*(k+1) —
                                      # more per emitted token at low
                                      # acceptance)
    cached_tokens: int = 0            # prompt tokens served from the
                                      # prefix cache (no prefill compute)
    prefill_tokens: int = 0           # prompt tokens actually computed
                                      # at admission (= prompt length on
                                      # a miss; the unique suffix on a
                                      # prefix-cache hit)

    def ttft_s(self) -> Optional[float]:
        """Time to first token (arrival to first emission); ``None``
        until the first token exists."""
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    def tpot_s(self) -> Optional[float]:
        """Time per output token after the first (decode cadence)."""
        if self.done_s is None or self.first_token_s is None:
            return None
        n = max(1, len(self.output or []) - 1)
        return (self.done_s - self.first_token_s) / n


@dataclasses.dataclass
class _PrefillProgress:
    """Host cursor of one chunked prefill in flight: the slot is held,
    its pages are pinned, and ``next_pos`` prompt tokens are absorbed
    so far (the device table row stays garbage until the final chunk
    installs the slot)."""

    r: Request
    prompt: Any          # (1, S) device prompt (incl. resumed output)
    toks: tuple          # host copy of the same tokens
    row: list            # physical pages, position order
    row_arr: Any         # (pages_per_slot,) padded device row
    next_pos: int        # absolute position of the next chunk
    budget: int          # decode budget at this admission
    resume: bool         # parked-request resume (stamps differ)
    cached: int          # prefix-cache tokens skipped at acquire


@dataclasses.dataclass
class _ServeCtx:
    """Mutable host state of one ``serve`` call, shared by the
    admission/prefill/decode helpers."""

    slots: list          # per-slot in-flight Request (None = free)
    slot_left: list      # host shadow of the device `remaining` vector
    filling: dict        # slot -> _PrefillProgress (chunked prefill)
    ready: Any           # deque of arrived, unadmitted requests
    parked: set          # rids of preempted requests awaiting resume
    done: list           # completed requests
    now: Callable[[], float]
    t0: float


class ServeEngine:
    """Fixed-batch engine (the seed baseline, kept for comparison)."""

    def __init__(self, model, params, *, max_len: int = 256,
                 batch_size: int = 8,
                 rules: Optional[ShardingRules] = None):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.batch = batch_size
        self.rules = rules
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl)

    def _prefill_impl(self, params, inputs):
        with sharding_ctx(self.rules):
            return self.model.prefill(params, inputs, max_len=self.max_len)

    def _decode_impl(self, params, cache, tokens):
        with sharding_ctx(self.rules):
            return self.model.decode_step(params, cache, tokens)

    # ------------------------------------------------------------------
    def run_batch(self, requests: list[Request],
                  now: Callable[[], float] = time.monotonic,
                  extra_inputs: Optional[dict] = None) -> list[Request]:
        """Service one batch of requests synchronously."""
        assert len(requests) <= self.batch
        reqs = requests
        prompts = jnp.stack([jnp.asarray(r.prompt, jnp.int32)
                             for r in reqs])
        inputs = {"tokens": prompts}
        if extra_inputs:
            inputs.update(extra_inputs)
        logits, cache = self._prefill(self.params, inputs)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        t_first = now()
        outs = [[int(t)] for t in tok[:, 0]]
        for r in reqs:
            r.first_token_s = t_first
        steps = max(r.max_new_tokens for r in reqs) - 1
        for _ in range(max(0, steps)):
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            for i, t in enumerate(tok[:, 0]):
                outs[i].append(int(t))
        t_done = now()
        for i, r in enumerate(reqs):
            r.output = outs[i][: r.max_new_tokens]
            r.done_s = t_done
        return reqs

    def tokens_per_request(self, requests: list[Request]) -> int:
        """Total emitted tokens (the efficiency denominators' work)."""
        return sum(len(r.output or []) for r in requests)


class ContinuousBatchingEngine:
    """Slot-based continuous batching with an on-device sampling loop.

    Usage::

        eng = ContinuousBatchingEngine(model, params, max_len=96,
                                       n_slots=4, chunk_steps=8)
        done = eng.serve(requests)          # honors Request.arrival_s

    Per decode chunk the host performs exactly one device->host sync
    (``sched_stats["decode_chunks"]`` counts the chunks); tokens, greedy
    sampling, per-slot position advance and done flags all stay on
    device inside a ``lax.fori_loop``.

    Speculative decoding (``spec_k > 0`` with a ``draft_model``): each
    chunk runs ``chunk_steps`` draft-and-verify rounds instead of
    ``chunk_steps`` single-token steps.  Per round every live slot
    drafts ``spec_k`` tokens with the small draft model, the target
    scores the whole window in one multi-token ``verify_step`` forward,
    and acceptance (greedy exact-match, or rejection sampling at
    ``temperature > 0`` — see ``repro.serving.speculative``) commits a
    per-slot prefix plus one bonus token.  Accepted lengths are ragged
    across slots; per-slot write offsets keep the emitted-token buffer
    contiguous so the host still syncs exactly once per chunk.  The KV
    cache rolls rejected tokens back in place: only the per-slot
    position advances, so stale rows sit beyond the frontier and the
    next verify window overwrites them.  Greedy speculative output is
    token-identical to plain greedy decode for any draft model.
    """

    def __init__(self, model, params, *, max_len: int = 256,
                 n_slots: int = 8, chunk_steps: int = 8,
                 rules: Optional[ShardingRules] = None,
                 draft_model=None, draft_params=None, spec_k: int = 0,
                 temperature: float = 0.0, spec_seed: int = 0,
                 kv_page_size: int = 0, kv_pages: Optional[int] = None,
                 prefix_caching: bool = False,
                 prefill_chunk_tokens: int = 0,
                 scheduler=None):
        self.model = model
        # the model the jitted bodies trace through: ``model`` here; the
        # tensor-parallel subclass swaps in its per-shard local model
        # (same code, head/FFN dims divided by tp) after super().__init__
        self.compute_model = model
        self.params = params
        self.max_len = max_len
        self.n_slots = n_slots
        self.chunk_steps = chunk_steps
        self.rules = rules
        self.spec_k = int(spec_k)
        self.speculative = self.spec_k > 0
        if self.speculative and draft_model is None:
            raise ValueError("spec_k > 0 needs draft_model/draft_params")
        self.draft_model = draft_model
        # like ``compute_model`` but for the draft: the tensor-parallel
        # subclass keeps the draft replicated (every shard runs the full
        # small model), so this stays ``draft_model`` there too
        self.draft_compute_model = draft_model
        self.draft_params = draft_params
        self.temperature = float(temperature)
        self.spec_seed = spec_seed
        if (self.speculative and
                draft_model.cfg.vocab_size != model.cfg.vocab_size):
            raise ValueError(
                f"draft vocab {draft_model.cfg.vocab_size} != target "
                f"vocab {model.cfg.vocab_size}")
        # speculative accounting (host-accumulated, reset per serve):
        # rounds/proposed/accepted over live slots, prefill token counts
        self.spec_stats = self._zero_spec_stats()
        # paged KV: block-granular cache through a per-slot page table,
        # with optional radix prefix caching on top (shared prompt
        # prefixes reuse pages by refcount bump instead of re-prefilling)
        self.page_size = int(kv_page_size)
        self.paged = self.page_size > 0
        self.prefix_caching = bool(prefix_caching)
        if self.prefix_caching and not self.paged:
            raise ValueError("prefix_caching requires kv_page_size > 0")
        self.page_pool: Optional[PagePool] = None
        self.prefix_cache: Optional[PrefixCache] = None
        if self.paged:
            if max_len % self.page_size:
                raise ValueError(
                    f"max_len {max_len} not a multiple of kv_page_size "
                    f"{self.page_size}")
            self.pages_per_slot = max_len // self.page_size
            # +1: physical page 0 is the reserved garbage page
            self.n_pages = (int(kv_pages) if kv_pages is not None
                            else n_slots * self.pages_per_slot + 1)
            if self.n_pages < self.pages_per_slot + 1:
                raise ValueError(
                    f"kv_pages {self.n_pages} cannot hold even one "
                    f"full slot ({self.pages_per_slot} pages) plus the "
                    f"garbage page")
            self.page_pool = PagePool(self.n_pages, self.page_size)
            if self.prefix_caching:
                self.prefix_cache = PrefixCache(self.page_pool,
                                                self.page_size)
        self.prefix_stats = self._zero_prefix_stats()
        # SLO-aware serving: chunked prefill + pluggable admission
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.chunked_prefill = self.prefill_chunk_tokens > 0
        if self.chunked_prefill and not self.paged:
            raise ValueError(
                "prefill_chunk_tokens > 0 requires kv_page_size > 0 — "
                "chunked prefill writes each chunk's K/V through the "
                "paged verify path at absolute positions")
        self.scheduler = scheduler
        if (scheduler is not None and scheduler.preemption
                and not self.prefix_caching):
            raise ValueError(
                "Scheduler(preemption=True) requires "
                "prefix_caching=True — a parked request's KV pages "
                "survive as prefix-cache entries until resume")
        self.sched_stats = self._zero_sched_stats()
        self._prefill_slot = jax.jit(self._prefill_slot_impl,
                                     donate_argnums=(2,))
        self._decode_chunk = jax.jit(self._decode_chunk_impl,
                                     donate_argnums=(1,))
        self._spec_chunk = jax.jit(self._spec_chunk_impl,
                                   donate_argnums=(2,))
        self._extend_slot = jax.jit(self._extend_slot_impl,
                                    donate_argnums=(2,))
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                      donate_argnums=(1,))
        self._install_slot = jax.jit(self._install_slot_impl,
                                     donate_argnums=(0,))
        self._step_logits = jax.jit(self._step_logits_impl)
        self.reset()

    @staticmethod
    def _zero_spec_stats() -> dict:
        return {"rounds": 0, "proposed": 0, "accepted": 0, "emitted": 0,
                "draft_fwd": 0, "draft_prefill_tokens": 0,
                "target_prefill_tokens": 0}

    @staticmethod
    def _zero_prefix_stats() -> dict:
        return {"lookups": 0, "hits": 0, "cached_tokens": 0,
                "evicted_pages": 0}

    @staticmethod
    def _zero_sched_stats() -> dict:
        return {"preemptions": 0, "resumes": 0, "prefill_chunks": 0,
                "decode_chunks": 0, "interleaved_chunks": 0}

    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.spec_stats["accepted"] / max(
            1, self.spec_stats["proposed"])

    # -- device state ---------------------------------------------------
    def reset(self):
        """Fresh slot state: empty cache, zero positions, no budgets."""
        if self.paged:
            cache = self._zeros(self.model.paged_cache_specs(
                self.n_slots, self.n_pages, self.page_size,
                self.pages_per_slot))
            self.page_pool.reset()
            if self.prefix_cache is not None:
                self.prefix_cache.reset()
            # host shadow of page ownership: pages each slot holds a
            # reference on (the device side only sees the table row)
            self._slot_pages: list[list[int]] = [
                [] for _ in range(self.n_slots)]
            # preemption shadows: the token history whose K/V occupies
            # the slot (admitted prompt, incl. resumed output) and how
            # many output tokens predate this admission — enough to
            # reconstruct the parked state host-side
            self._slot_toks: list[tuple] = [()] * self.n_slots
            self._slot_base: list[int] = [0] * self.n_slots
        else:
            cache = self._zeros(self.model.cache_specs(
                self.n_slots, self.max_len, per_slot_pos=True))
        self.state = {
            "cache": cache,
            "tok": jnp.zeros((self.n_slots,), jnp.int32),
            "remaining": jnp.zeros((self.n_slots,), jnp.int32),
        }
        if self.speculative:
            self.state["draft_cache"] = self.draft_model.init_cache(
                self.n_slots, self.max_len, per_slot_pos=True)
            if self.temperature > 0:
                self.state["key"] = jax.random.PRNGKey(self.spec_seed)

    def _zeros(self, specs):
        """Zero-filled device arrays for the target cache's
        ``ShapeDtypeStruct`` tree (the tensor-parallel subclass creates
        them straight into their shardings)."""
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    def _prefill_slot_impl(self, params, dparams, state, tokens, slot,
                           budget, pages=None):
        """Prefill one prompt and splice it into slot ``slot``.

        ``tokens``: (1, S) prompt.  The batch-1 prefill cache is
        scattered into batch row ``slot`` of every layer's state (batch
        is axis 1 of the stacked layer trees), the slot's position is
        set to the prompt length, and the first greedy token seeds the
        decode loop.  Unrelated slots' cache rows are untouched.  In
        speculative mode the draft model prefills the same prompt into
        its own cache (outside any tensor-parallel context — the draft
        runs replicated), so drafting starts aligned with the target.

        Paged mode passes ``pages`` (the slot's full page-table row,
        (pages_per_slot,) int32): the contiguous batch-1 prefill cache
        is chopped into page_size blocks and scattered at the row's
        physical pages.  Padded row entries are the garbage page 0, so
        the blocks past the request's allocation land there — page 0's
        contents are only ever read through masked (score = -1e30)
        attention positions, so clobbering it is harmless.
        """

        def splice(cache, logits_and_one):
            logits, one = logits_and_one
            layers = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), slot, axis=1),
                cache["layers"], one["layers"])
            pos = cache["pos"].at[slot].set(one["pos"].astype(jnp.int32))
            return {"layers": layers, "pos": pos}

        def splice_paged(cache, one):
            pps = pages.shape[0]
            ps = self.page_size

            def scatter(pool, small):
                blocks = small[:, 0].reshape(
                    small.shape[0], pps, ps, *small.shape[3:])
                return _scatter_pages(pool, blocks, pages)

            layers = jax.tree.map(scatter, cache["layers"],
                                  one["layers"])
            pos = cache["pos"].at[slot].set(one["pos"].astype(jnp.int32))
            table = cache["pages"].at[slot].set(pages)
            return {"layers": layers, "pos": pos, "pages": table}

        with sharding_ctx(self.rules):
            logits, one = self.compute_model.prefill(
                params, {"tokens": tokens}, max_len=self.max_len)
        tok0 = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)
        new = dict(
            state,
            cache=(splice_paged(state["cache"], one) if pages is not None
                   else splice(state["cache"], (logits, one))),
            tok=state["tok"].at[slot].set(tok0),
            remaining=state["remaining"].at[slot].set(
                jnp.maximum(budget - 1, 0)),
        )
        if self.speculative:
            with sharding_ctx(None), tp_ctx(None):
                dlogits, done = self.draft_compute_model.prefill(
                    dparams, {"tokens": tokens}, max_len=self.max_len)
            new["draft_cache"] = splice(state["draft_cache"],
                                        (dlogits, done))
        return new, tok0

    def _extend_slot_impl(self, params, dparams, state, tokens, suffix,
                          slot, pages, start, budget):
        """Admit a prefix-cache hit: only the unique suffix is computed.

        ``tokens``: (1, S) full prompt; ``suffix``: (1, S - start) the
        part not covered by cached pages (``lookup`` guarantees it is
        non-empty).  K/V are stored post-RoPE at absolute positions, so
        the shared pages already hold exactly what a full prefill would
        have written; the suffix runs through a batch-1 paged
        ``verify_step`` sharing the engine's pool leaves, starting at
        absolute position ``start``, and its last logit row seeds
        decoding just like a full prefill.  In speculative mode the
        draft still prefills the *full* prompt — its contiguous cache
        has no pages to share.
        """
        cache = state["cache"]
        mini = {"layers": cache["layers"],
                "pos": start[None].astype(jnp.int32),
                "pages": pages[None]}
        with sharding_ctx(self.rules):
            logits, mini = self.compute_model.verify_step(
                params, mini, suffix)
        tok0 = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)
        pos = cache["pos"].at[slot].set(
            (start + suffix.shape[1]).astype(jnp.int32))
        table = cache["pages"].at[slot].set(pages)
        new = dict(
            state,
            cache={"layers": mini["layers"], "pos": pos, "pages": table},
            tok=state["tok"].at[slot].set(tok0),
            remaining=state["remaining"].at[slot].set(
                jnp.maximum(budget - 1, 0)),
        )
        if self.speculative:
            with sharding_ctx(None), tp_ctx(None):
                dlogits, done = self.draft_compute_model.prefill(
                    dparams, {"tokens": tokens}, max_len=self.max_len)
            dc = state["draft_cache"]
            dlayers = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), slot, axis=1),
                dc["layers"], done["layers"])
            new["draft_cache"] = {
                "layers": dlayers,
                "pos": dc["pos"].at[slot].set(
                    done["pos"].astype(jnp.int32)),
            }
        return new, tok0

    def _prefill_chunk_impl(self, params, state, chunk, pages, start):
        """Absorb one prompt chunk into a slot's pages (chunked prefill).

        ``chunk``: (1, C) prompt slice at absolute positions
        ``[start, start + C)``; ``pages``: the slot's padded page-table
        row.  The chunk runs through the batch-1 paged ``verify_step``
        sharing the engine's pool leaves — K/V land post-RoPE at
        absolute positions, so after the last chunk the pages hold
        exactly what one monolithic prefill would have written.  Only
        the pool leaves change here: the slot's device table row, pos,
        token and budget are installed by the *final* chunk (an
        ``_extend_slot`` call), so concurrent decode chunks never see a
        half-filled slot (their garbage writes for this still-inactive
        slot land on the reserved garbage page).  The chunk's logits
        are discarded — no token exists until the prompt completes.
        """
        cache = state["cache"]
        mini = {"layers": cache["layers"],
                "pos": start[None].astype(jnp.int32),
                "pages": pages[None]}
        with sharding_ctx(self.rules):
            _, mini = self.compute_model.verify_step(params, mini, chunk)
        return dict(state, cache=dict(cache, layers=mini["layers"]))

    def _install_slot_impl(self, state, blocks, tok0, slot, pages,
                           row, n_tokens, budget):
        """Install handed-off K/V blocks into slot ``slot``
        (prefill/decode disaggregation).

        ``blocks``: per-layer K/V trees of shape (L, NB, page, kvh, dh)
        computed by a prefill replica; they are scattered at physical
        ``pages`` (the (NB,) prompt pages) of this engine's pool,
        transposed there to the pool's KV-head-major layout.
        ``row`` is the slot's full padded table row, ``n_tokens`` the
        prompt length, ``tok0`` the first token the prefill replica
        already emitted.  After this the slot decodes exactly as if it
        had prefilled locally — ``_prefill_slot`` minus the compute.
        """
        cache = state["cache"]

        layers = jax.tree.map(
            lambda pool, small: _scatter_pages(pool, small, pages),
            cache["layers"], blocks)
        pos = cache["pos"].at[slot].set(n_tokens.astype(jnp.int32))
        table = cache["pages"].at[slot].set(row)
        return dict(
            state,
            cache={"layers": layers, "pos": pos, "pages": table},
            tok=state["tok"].at[slot].set(tok0),
            remaining=state["remaining"].at[slot].set(
                jnp.maximum(budget - 1, 0)),
        )

    def _decode_chunk_impl(self, params, state):
        """Decode ``chunk_steps`` tokens for every live slot on device.

        Inactive slots (remaining == 0) hold: their position does not
        advance and their last token is re-emitted into the buffer (the
        host ignores those rows).  Their cache row does receive a
        garbage write at its frozen position, which is safe: the row is
        fully overwritten by the next prefill-into-slot.
        """
        def body(i, st):
            cache, tok, remaining, buf = st
            active = remaining > 0
            pos_prev = cache["pos"]
            with sharding_ctx(self.rules):
                logits, cache = self.compute_model.decode_step(
                    params, cache, tok[:, None])
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            tok = jnp.where(active, nxt, tok)
            cache = dict(cache, pos=jnp.where(active, pos_prev + 1,
                                              pos_prev))
            buf = jax.lax.dynamic_update_slice(buf, tok[:, None], (0, i))
            remaining = remaining - active.astype(jnp.int32)
            return (cache, tok, remaining, buf)

        buf0 = jnp.zeros((self.n_slots, self.chunk_steps), jnp.int32)
        cache, tok, remaining, buf = _bounded_while(
            self.chunk_steps, lambda st: jnp.any(st[2] > 0), body,
            (state["cache"], state["tok"], state["remaining"], buf0))
        return dict(state, cache=cache, tok=tok, remaining=remaining), buf

    def _step_logits_impl(self, params, state):
        """Logits (B, Vp) of every slot's next decode step; the state
        is read, not advanced."""
        with sharding_ctx(self.rules):
            logits, _ = self.compute_model.decode_step(
                params, state["cache"], state["tok"][:, None])
        return logits[:, -1]

    def first_step_logits(self, prompt) -> jax.Array:
        """Prefill ``prompt`` into slot 0 of a fresh state and return
        the logits (Vp,) of its first decode step.

        A parity probe: two kernel paths or two layouts of the same
        weights must give the same row.  The prompt is admitted exactly
        as ``serve`` admits it (paged or contiguous, chunked or not).
        """
        self.reset()
        r = Request(rid=0, prompt=prompt, max_new_tokens=2)
        cx = _ServeCtx(slots=[None] * self.n_slots,
                       slot_left=[0] * self.n_slots, filling={},
                       ready=collections.deque(), parked=set(), done=[],
                       now=time.monotonic, t0=time.monotonic())
        if not self._admit_slot(r, 0, cx):
            raise RuntimeError("probe prompt does not fit the page pool")
        while cx.filling:
            self._advance_prefill(0, cx)
        return self._step_logits(self.params, self.state)[0]

    def _spec_chunk_impl(self, params, dparams, state):
        """Run ``chunk_steps`` draft-and-verify rounds fully on device.

        Each round: the draft model decodes ``spec_k`` tokens per slot
        (replicated, outside any TP context), the target scores the
        window ``[tok, d_1..d_k]`` in one ``verify_step`` forward, and
        acceptance commits ``a + 1`` tokens per slot (``a`` accepted
        drafts plus the bonus/resampled token).  Ragged accepted
        lengths stay in lockstep via per-slot write offsets into the
        emitted-token buffer; only the per-slot position advances, so
        rejected tokens roll back in place.  Inactive slots hold
        exactly as in the plain chunk (frozen pos/tok; their window
        writes are garbage the next prefill-into-slot overwrites).

        Returns ``(state, out)``; ``out["buf"]`` is (B, rounds, k+1)
        with ``out["n_emit"]`` (B, rounds) valid-prefix lengths — the
        host stitches each slot's tokens from the per-round blocks (a
        fixed-index block write per round beats a ragged scatter at
        per-slot offsets).  One host sync fetches it all.
        """
        k = self.spec_k
        b = self.n_slots
        sampled = self.temperature > 0

        def draft_loop(dcache, tok, key_round):
            """Draft k tokens per slot with the (replicated) draft.

            Runs k + 1 decode steps: step j processes the token at
            window offset j (writing its K/V at ``pos + j``) and emits
            proposal j + 1.  The final step processes d_k purely to
            fill its cache row — on a fully-accepted window the next
            round starts past d_k, so its K/V must exist; for
            partially-accepted slots that row sits beyond the new
            frontier and the next window write overwrites it.  Its
            sampled output is discarded.
            """
            vp = getattr(self.draft_compute_model, "vp", 0)
            toks0 = jnp.zeros((b, k + 1), jnp.int32)
            dlog0 = (jnp.zeros((b, k + 1, vp), jnp.float32) if sampled
                     else jnp.zeros((b, k + 1, 1), jnp.float32))

            def step(j, ds):
                dc, cur, toks, dlog = ds
                with sharding_ctx(None), tp_ctx(None):
                    logits, dc = self.draft_compute_model.decode_step(
                        dparams, dc, cur[:, None])
                row = logits[:, -1].astype(jnp.float32)
                if sampled:
                    nxt = jax.random.categorical(
                        jax.random.fold_in(key_round, j),
                        row / self.temperature, axis=-1)
                    dlog = jax.lax.dynamic_update_slice(
                        dlog, row[:, None], (0, j, 0))
                else:
                    nxt = jnp.argmax(row, axis=-1)
                nxt = nxt.astype(jnp.int32)
                toks = jax.lax.dynamic_update_slice(
                    toks, nxt[:, None], (0, j))
                return (dc, nxt, toks, dlog)

            dc, _, toks, dlog = jax.lax.fori_loop(
                0, k + 1, step, (dcache, tok, toks0, dlog0))
            return dc, toks[:, :k], dlog[:, :k]

        def round_body(i, st):
            active = st["remaining"] > 0
            pos0 = st["cache"]["pos"]
            dpos0 = st["draft_cache"]["pos"]
            key_round = (jax.random.fold_in(st["key"], i)
                         if sampled else None)
            dcache, draft_toks, dlog = draft_loop(
                st["draft_cache"], st["tok"], key_round)
            vtoks = jnp.concatenate([st["tok"][:, None], draft_toks],
                                    axis=1)                   # (B, k+1)
            with sharding_ctx(self.rules):
                logits, cache = self.compute_model.verify_step(
                    params, st["cache"], vtoks)
            if sampled:
                acc, out_toks = speculative_sample(
                    jax.random.fold_in(key_round, k + 1),
                    logits.astype(jnp.float32), dlog,
                    draft_toks, self.temperature)
            else:
                acc, out_toks = greedy_verify(logits, draft_toks)
            n_emit = jnp.where(active, acc + 1, 0)
            new_tok = jnp.take_along_axis(out_toks, acc[:, None],
                                          axis=1)[:, 0]
            # fixed-index block write: round i owns buf[:, i, :]
            buf = jax.lax.dynamic_update_slice(
                st["buf"], out_toks[:, None], (0, i, 0))
            new = dict(
                st,
                cache=dict(cache, pos=pos0 + n_emit),
                draft_cache=dict(dcache,
                                 pos=jnp.where(active, pos0 + n_emit,
                                               dpos0)),
                tok=jnp.where(active, new_tok, st["tok"]),
                remaining=jnp.maximum(st["remaining"] - n_emit, 0),
                buf=buf,
                n_emit=jax.lax.dynamic_update_slice(
                    st["n_emit"], n_emit[:, None], (0, i)),
                accepted=st["accepted"] + jnp.where(active, acc, 0),
                proposed=st["proposed"] + active.astype(jnp.int32) * k,
                draft_fwd=st["draft_fwd"]
                + active.astype(jnp.int32) * (k + 1),
            )
            return new

        zeros = jnp.zeros((b,), jnp.int32)
        st = dict(state,
                  buf=jnp.zeros((b, self.chunk_steps, k + 1), jnp.int32),
                  n_emit=jnp.zeros((b, self.chunk_steps), jnp.int32),
                  accepted=zeros, proposed=zeros, draft_fwd=zeros)
        if sampled:
            key, sub = jax.random.split(state["key"])
            st["key"] = sub
        st = _bounded_while(self.chunk_steps,
                            lambda s: jnp.any(s["remaining"] > 0),
                            round_body, st)
        out = {name: st.pop(name)
               for name in ("buf", "n_emit", "accepted", "proposed",
                            "draft_fwd")}
        if sampled:
            st["key"] = key
        return st, out

    # -- paged admission (host side) -------------------------------------
    def _alloc_pages(self, n: int) -> list[int]:
        """Allocate ``n`` pages, evicting cache-only prefix pages under
        memory pressure.  Raises ``PoolExhausted`` when eviction cannot
        free enough (every remaining page is pinned by a live slot)."""
        pool = self.page_pool
        if n > pool.free_pages() and self.prefix_cache is not None:
            self.prefix_stats["evicted_pages"] += self.prefix_cache.evict(
                n - pool.free_pages())
        return pool.alloc(n)

    def _release_slot(self, b: int) -> None:
        """Drop a retired slot's page references, aiming its table row
        at the garbage page *first*: the frozen chunk loop keeps
        scattering at the dead slot's position, and those writes must
        not land on pages that may be reallocated to another request."""
        if not self.paged:
            return
        cache = self.state["cache"]
        self.state["cache"] = dict(
            cache, pages=cache["pages"].at[b].set(GARBAGE_PAGE))
        for p in self._slot_pages[b]:
            self.page_pool.unref(p)
        self._slot_pages[b] = []

    def _acquire_pages(self, toks: tuple, s: int,
                       budget: int) -> tuple[list, int]:
        """Pin prefix-cache hit pages and allocate the fresh remainder
        for a prompt of ``s`` tokens decoding up to ``budget`` more.

        Order matters: hit pages are ``ref``-ed *before* allocating,
        because allocation may evict — pinning first means eviction can
        never free a page this request is about to read.  On
        ``PoolExhausted`` the pins are rolled back and the exception
        propagates (the caller defers or preempts).  Returns ``(row,
        start)``: the physical pages in position order and the
        cached-token count (``len(shared) * page_size``).
        """
        ps = self.page_size
        n_blocks = min(self.pages_per_slot,
                       -(-(s + budget + self.spec_k) // ps))
        shared = (self.prefix_cache.lookup(toks)
                  if self.prefix_cache is not None else [])
        for p in shared:
            self.page_pool.ref(p)
        try:
            fresh = self._alloc_pages(n_blocks - len(shared))
        except PoolExhausted:
            for p in shared:
                self.page_pool.unref(p)
            raise
        if self.prefix_cache is not None:
            self.prefix_stats["lookups"] += 1
            if shared:
                self.prefix_stats["hits"] += 1
                self.prefix_stats["cached_tokens"] += len(shared) * ps
        return shared + fresh, len(shared) * ps

    def _intern_prompt(self, toks: tuple, row: list) -> None:
        """Intern the *full* blocks of ``toks`` (physical pages
        ``row``) into the prefix cache, once their K/V exists.  Only
        full blocks: a partial last block still receives its slot's
        decode writes, so sharing it would let another request read
        tokens that aren't prompt."""
        if self.prefix_cache is None:
            return
        n_full = min(len(toks) // self.page_size, len(row))
        self.prefix_cache.insert(toks[:n_full * self.page_size],
                                 row[:n_full])

    def _park(self, b: int, cx: "_ServeCtx") -> Request:
        """Preempt slot ``b``: evict its pages, park its state host-side.

        The slot's decode-complete K/V — its admitted token history
        plus every emitted token but the pending one — is interned
        block-wise into the prefix cache, whose reference keeps those
        pages alive after ``_release_slot`` drops the slot's own refs
        (the partial last block frees immediately).  The request is
        re-queued carrying its output; readmission resumes it through
        the prefix-cache extend path with ``prompt' = prompt +
        output``, recomputing at most one block's worth of tail and
        emitting the continuation token — bit-identical to never
        having been preempted.  Under later pool pressure the parked
        pages may themselves be evicted (refcount 1, cache-only),
        degrading resume to a longer recompute but never to wrong
        tokens.
        """
        r = cx.slots[b]
        emitted = r.output[self._slot_base[b]:]
        # K/V exists for history + emitted[:-1]; emitted[-1] is the
        # pending decode input (its K/V row is written next step)
        hist = self._slot_toks[b] + tuple(emitted[:-1])
        self._intern_prompt(hist, self._slot_pages[b])
        self._release_slot(b)
        # freeze the device slot so the chunk loop stops decoding it
        self.state = dict(
            self.state,
            remaining=self.state["remaining"].at[b].set(0))
        cx.slots[b] = None
        cx.slot_left[b] = 0
        r.preemptions += 1
        self.sched_stats["preemptions"] += 1
        return r

    def _admit_slot(self, r: Request, b: int, cx: "_ServeCtx") -> bool:
        """Admit ``r`` into free slot ``b`` (or start its chunked
        prefill); ``False`` = defer, pool pressure survived preemption.

        A parked request (rid in ``cx.parked``) resumes with
        ``prompt' = prompt + output`` and the remaining decode budget;
        the prefix-cache lookup inside ``_acquire_pages`` finds the
        parked full blocks, so only the tail recomputes.
        """
        resume = r.rid in cx.parked
        if resume:
            toks = (tuple(int(x) for x in np.asarray(r.prompt)
                          .reshape(-1)) + tuple(r.output))
            budget = r.max_new_tokens - len(r.output)
        else:
            toks = tuple(int(x) for x in np.asarray(r.prompt).reshape(-1))
            budget = r.max_new_tokens
        prompt = jnp.asarray(toks, jnp.int32)[None]
        s = int(prompt.shape[1])
        # speculative verify windows write up to spec_k rows past the
        # last decoded position; keep them in-cache
        assert s + budget + self.spec_k <= self.max_len, \
            (s, budget, self.spec_k, self.max_len)
        if not self.paged:
            r.prefill_tokens += s
            self.state, tok0 = self._prefill_slot(
                self.params, self.draft_params, self.state, prompt,
                jnp.asarray(b, jnp.int32), jnp.asarray(budget, jnp.int32))
            self._finish_admit(r, b, tok0, resume, budget, s, 0, cx)
            return True
        row = None
        try:
            row, start = self._acquire_pages(toks, s, budget)
        except PoolExhausted:
            if self.scheduler is not None:
                running = [(i, cx.slots[i]) for i in range(self.n_slots)
                           if cx.slots[i] is not None]
                while running:
                    v = self.scheduler.pick_victim(running, r)
                    if v is None:
                        break
                    victim = self._park(v, cx)
                    cx.parked.add(victim.rid)
                    cx.ready.append(victim)
                    running = [iq for iq in running if iq[0] != v]
                    try:
                        row, start = self._acquire_pages(toks, s, budget)
                        break
                    except PoolExhausted:
                        continue
            if row is None:
                return False
        self._slot_pages[b] = list(row)
        self._slot_toks[b] = toks
        self._slot_base[b] = len(r.output) if resume else 0
        row_arr = jnp.asarray(
            row + [GARBAGE_PAGE] * (self.pages_per_slot - len(row)),
            jnp.int32)
        r.cached_tokens += start
        r.prefill_tokens += s - start
        if self.chunked_prefill:
            cx.filling[b] = _PrefillProgress(
                r=r, prompt=prompt, toks=toks, row=list(row),
                row_arr=row_arr, next_pos=start, budget=budget,
                resume=resume, cached=start)
            return True
        budget_arr = jnp.asarray(budget, jnp.int32)
        if start:
            self.state, tok0 = self._extend_slot(
                self.params, self.draft_params, self.state, prompt,
                prompt[:, start:], jnp.asarray(b, jnp.int32), row_arr,
                jnp.asarray(start, jnp.int32), budget_arr)
        else:
            self.state, tok0 = self._prefill_slot(
                self.params, self.draft_params, self.state, prompt,
                jnp.asarray(b, jnp.int32), budget_arr, row_arr)
        self._intern_prompt(toks, row)
        self._finish_admit(r, b, tok0, resume, budget, s, start, cx)
        return True

    def _advance_prefill(self, b: int, cx: "_ServeCtx") -> None:
        """Run one prefill chunk for the filling slot ``b``; the final
        chunk installs the slot and emits its first token."""
        p = cx.filling[b]
        c = self.prefill_chunk_tokens
        s = int(p.prompt.shape[1])
        self.sched_stats["prefill_chunks"] += 1
        if s - p.next_pos > c:
            self.state = self._prefill_chunk(
                self.params, self.state,
                p.prompt[:, p.next_pos:p.next_pos + c], p.row_arr,
                jnp.asarray(p.next_pos, jnp.int32))
            p.next_pos += c
            return
        # final chunk: the extend path computes the tail, installs the
        # slot's table row / pos / budget and seeds decoding (the
        # speculative draft prefills the full prompt inside it)
        del cx.filling[b]
        self.state, tok0 = self._extend_slot(
            self.params, self.draft_params, self.state, p.prompt,
            p.prompt[:, p.next_pos:], jnp.asarray(b, jnp.int32),
            p.row_arr, jnp.asarray(p.next_pos, jnp.int32),
            jnp.asarray(p.budget, jnp.int32))
        self._intern_prompt(p.toks, p.row)
        self._finish_admit(p.r, b, tok0, p.resume, p.budget, s,
                           p.cached, cx)

    def _finish_admit(self, r: Request, b: int, tok0, resume: bool,
                      budget: int, s: int, start: int,
                      cx: "_ServeCtx") -> None:
        """Stamp and route a just-admitted request: emit its first (or
        continuation) token, account speculative prefill work, and
        either retire it or hand the slot to the decode loop."""
        with TraceAnnotation("engine.first_token", rid=r.rid):
            first = int(tok0)          # blocks -> true TTFT
        t_now = cx.now() - cx.t0
        if resume:
            r.output.append(first)
            cx.parked.discard(r.rid)
            self.sched_stats["resumes"] += 1
        else:
            r.first_token_s = t_now
            r.output = [first][: r.max_new_tokens]  # budget 0 -> []
        if self.speculative:
            # the draft prefilled the full prompt alongside the
            # target, which only computed the uncached part
            computed = s - start
            r.draft_tokens += s
            r.verify_tokens += computed
            self.spec_stats["draft_prefill_tokens"] += s
            self.spec_stats["target_prefill_tokens"] += computed
        if budget <= 1:
            r.done_s = t_now
            cx.done.append(r)
            self._release_slot(b)
        else:
            cx.slots[b] = r
            cx.slot_left[b] = budget - 1

    # -- host orchestration ---------------------------------------------
    def serve(self, requests: list[Request],
              now: Callable[[], float] = time.monotonic,
              sleep: Callable[[float], None] = time.sleep,
              honor_arrivals: bool = True) -> list[Request]:
        """Service ``requests``, admitting each at its ``arrival_s``.

        Returns the completed requests (arrival order not preserved —
        short requests overtake stragglers).  ``first_token_s`` and
        ``done_s`` are stamped in seconds since serve() start, i.e. on
        the same clock as ``arrival_s`` (so latency = done_s -
        arrival_s, and the stamps line up with Director power samples
        that start at t=0).  With ``honor_arrivals=False`` the queue is
        drained as fast as slots free up (Offline scenario).

        Admission is FIFO by arrival unless a ``scheduler`` was given
        (priority + deadline-slack ordering, optional preemption — see
        ``repro.serving.scheduler.Scheduler``).  With
        ``prefill_chunk_tokens > 0`` each loop iteration advances every
        in-flight prompt by one chunk *and* runs one decode chunk, so
        decoding slots keep emitting while long prompts fill (chunked
        prefill; token-identical to monolithic).  ``sched_stats``
        counts preemptions, resumes, and chunk interleaving per serve.

        ``admitted_s`` is stamped, on the same clock, when a request
        leaves the ready queue for an admission that succeeds (a
        deferral does not stamp; a resume keeps the first stamp; under
        chunked prefill the same iteration runs its first chunk), so
        ``admitted_s - arrival_s`` is its queue wait.  Every iteration
        and each host phase in it is a ``jax.profiler.TraceAnnotation``
        span (``engine.step`` around ``engine.admit`` >
        ``engine.first_token``, ``engine.prefill_chunk``,
        ``engine.decode`` > ``engine.decode_sync``, ``engine.stitch``
        and ``engine.wait_arrival``): a profiler that is running puts
        them on the host plane of its trace, on the device's clock;
        without one each costs about a microsecond.
        """
        counts = collections.Counter(r.rid for r in requests)
        dup = sorted(r for r, c in counts.items() if c > 1)
        if dup:                        # validate before touching state
            raise ValueError(
                f"duplicate request ids in admission queue: {dup} — "
                f"rids must be unique per serve() (derive them from "
                f"the loadgen qid, repro.core.loadgen.qid_of)")
        self.reset()
        self.spec_stats = self._zero_spec_stats()
        self.prefix_stats = self._zero_prefix_stats()
        self.sched_stats = self._zero_sched_stats()
        pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        cx = _ServeCtx(slots=[None] * self.n_slots,
                       slot_left=[0] * self.n_slots, filling={},
                       ready=collections.deque(), parked=set(),
                       done=[], now=now, t0=now())
        while (pending or cx.ready or cx.filling
               or any(s is not None for s in cx.slots)):
            with TraceAnnotation("engine.step"):
                t = now() - cx.t0
                while pending and (not honor_arrivals
                                   or pending[0].arrival_s <= t):
                    cx.ready.append(pending.popleft())
                if self.scheduler is not None and len(cx.ready) > 1:
                    ordered = self.scheduler.order(cx.ready, t)
                    cx.ready.clear()
                    cx.ready.extend(ordered)
                # admit arrived requests into free slots (prefill-into-
                # slot or, chunked, start the prompt's chunk cursor)
                for b in range(self.n_slots):
                    if (cx.slots[b] is not None or b in cx.filling
                            or not cx.ready):
                        continue
                    r = cx.ready.popleft()
                    t_admit = now() - cx.t0
                    with TraceAnnotation("engine.admit", rid=r.rid):
                        admitted = self._admit_slot(r, b, cx)
                    if not admitted:
                        # defer: a retiring slot will free its pages
                        cx.ready.appendleft(r)
                        if (not cx.filling and not any(
                                s is not None for s in cx.slots)):
                            raise RuntimeError(
                                f"request {r.rid} needs more KV pages "
                                f"than eviction can ever free (pool of "
                                f"{self.page_pool.n_pages - 1} usable "
                                f"pages)")
                        break
                    if r.admitted_s is None:   # a resume keeps the first
                        r.admitted_s = t_admit
                # chunked prefill: one chunk per filling slot per iteration
                for b in list(cx.filling):
                    with TraceAnnotation("engine.prefill_chunk",
                                         rid=cx.filling[b].r.rid):
                        self._advance_prefill(b, cx)
                if not any(s is not None for s in cx.slots):
                    if cx.filling:
                        continue           # keep chunking the prompt(s)
                    if not cx.ready:
                        if not pending:
                            break
                        if honor_arrivals:
                            dt = pending[0].arrival_s - (now() - cx.t0)
                            if dt > 0:
                                with TraceAnnotation("engine.wait_arrival"):
                                    sleep(dt)
                    continue
                self._decode_and_stitch(cx)
        return cx.done

    def _decode_and_stitch(self, cx: "_ServeCtx") -> None:
        """One fused multi-token decode chunk for every live slot with a
        single host sync after it, then the per-slot stitch: each
        slot's tokens join its request, and a request whose budget is
        spent retires and frees its slot."""
        with TraceAnnotation("engine.decode"):
            if self.speculative:
                self.state, out = self._spec_chunk(
                    self.params, self.draft_params, self.state)
                with TraceAnnotation("engine.decode_sync"):
                    out = jax.device_get(out)
                buf_np = np.asarray(out["buf"])      # (B, rounds, k+1)
                n_emit = np.asarray(out["n_emit"])   # (B, rounds)
            else:
                self.state, buf = self._decode_chunk(self.params,
                                                     self.state)
                with TraceAnnotation("engine.decode_sync"):
                    buf_np = np.asarray(jax.device_get(buf))
        self.sched_stats["decode_chunks"] += 1
        if cx.filling:                 # decode emitted while a prompt
            self.sched_stats["interleaved_chunks"] += 1  # filled
        t_chunk = cx.now() - cx.t0
        with TraceAnnotation("engine.stitch"):
            for b in range(self.n_slots):
                r = cx.slots[b]
                if r is None:
                    continue
                if self.speculative:
                    # stitch the slot's tokens from its per-round blocks
                    toks = [int(x) for i in range(buf_np.shape[1])
                            for x in buf_np[b, i, :n_emit[b, i]]]
                else:
                    toks = [int(x) for x in buf_np[b]]
                take = min(cx.slot_left[b], len(toks))
                r.output.extend(toks[:take])
                cx.slot_left[b] -= take
                if self.speculative:
                    rounds_b = int((n_emit[b] > 0).sum())
                    r.draft_tokens += int(out["draft_fwd"][b])
                    r.verify_tokens += rounds_b * (self.spec_k + 1)
                    self.spec_stats["rounds"] += rounds_b
                    self.spec_stats["proposed"] += int(out["proposed"][b])
                    self.spec_stats["accepted"] += int(out["accepted"][b])
                    self.spec_stats["draft_fwd"] += int(out["draft_fwd"][b])
                    self.spec_stats["emitted"] += take
                if cx.slot_left[b] == 0:    # retire; slot free to refill
                    r.done_s = t_chunk
                    cx.done.append(r)
                    cx.slots[b] = None
                    self._release_slot(b)

    def tokens_per_request(self, requests: list[Request]) -> int:
        """Total emitted tokens (the efficiency denominators' work)."""
        return sum(len(r.output or []) for r in requests)


def attribute_request_energy(requests: list[Request],
                             times_s: np.ndarray,
                             watts: np.ndarray,
                             weight: Optional[Callable[[Request], float]]
                             = None) -> dict[int, float]:
    """Split measured system energy across in-flight requests.

    ``times_s``/``watts``: the Director's power samples (seconds since
    run start — the same clock the engine stamps requests on).  Each
    sample interval's energy is divided among the requests in flight
    (arrival <= t < done) during it; idle intervals are dropped.
    Fills ``Request.energy_j`` and returns {rid: joules}.

    ``weight``: optional per-request weighting, ``r -> float``.  By
    default every live request gets an equal share of an interval's
    energy; with a weight the split is proportional, and the shares of
    an interval still sum to its energy, so the per-request total still
    equals the measured busy-window total.  Speculative serving uses
    this to bill draft-model forwards to the request that triggered
    them (``r.draft_tokens`` scaled by the draft/target FLOP ratio) —
    without it a request with a low acceptance rate would be
    under-billed and per-request energy would no longer reflect what
    the fleet actually burned on it.
    """
    times_s = np.asarray(times_s, float)
    watts = np.asarray(watts, float)
    per: dict[int, float] = {r.rid: 0.0 for r in requests}
    w_of = ((lambda r: 1.0) if weight is None
            else (lambda r: max(float(weight(r)), 1e-12)))
    spans = [(r.rid, r.arrival_s, r.done_s, w_of(r)) for r in requests
             if r.done_s is not None]
    for i in range(len(times_s) - 1):
        t_lo, t_hi = times_s[i], times_s[i + 1]
        e = watts[i] * (t_hi - t_lo)
        live = [(rid, w) for rid, a, d, w in spans
                if a < t_hi and d > t_lo]
        if not live:
            continue
        w_sum = sum(w for _, w in live)
        for rid, w in live:
            per[rid] += e * w / w_sum
    for r in requests:
        r.energy_j = per.get(r.rid)
    return per
