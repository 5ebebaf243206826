"""SLO-aware serving: scheduler policy units, chunked-prefill TTFT
wins (on an injected clock) + token parity on a bimodal trace,
preempt/park/resume bit-identity under page-pool pressure, the
disaggregated prefill fleet's priority ordering, and the tail-SLO metric plumbing
(ServerMetrics.tail_attainment, max_sustainable_qps)."""
import dataclasses
import queue
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.efficiency import max_sustainable_qps, qps_at_slo_per_joule
from repro.core.loadgen import QuerySampleLibrary, run_server_queue
from repro.models import build_model
from repro.models.param import init_params
from repro.serving import (ContinuousBatchingEngine, DisaggregatedEngine,
                           Request, Scheduler)


def _build(arch="qwen3-1.7b", **overrides):
    cfg = reduce_config(get_config(arch))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    return cfg, model, params


def _req(rid, n_prompt, budget, arrival_s=0.0, priority=0,
         deadline_s=None, seed_off=0):
    rng = np.random.default_rng(1_000 + rid + seed_off)
    return Request(rid=rid, prompt=rng.integers(0, 512, n_prompt),
                   max_new_tokens=budget, arrival_s=arrival_s,
                   priority=priority, deadline_s=deadline_s)


def _by_rid(done):
    return {r.rid: tuple(r.output) for r in done}


# --- Scheduler policy (pure host-side) -----------------------------------

def test_scheduler_orders_by_priority_then_slack():
    s = Scheduler()
    a = _req(0, 4, 2, arrival_s=0.0, priority=0)            # best effort
    b = _req(1, 4, 2, arrival_s=0.1, priority=1,
             deadline_s=5.0)                                # loose
    c = _req(2, 4, 2, arrival_s=0.2, priority=1,
             deadline_s=1.0)                                # tight
    assert [r.rid for r in s.order([a, b, c], now_s=0.5)] == [2, 1, 0]
    # no deadline -> infinite slack: FIFO within the class
    d = _req(3, 4, 2, arrival_s=0.05, priority=1)
    assert [r.rid for r in s.order([a, d, b], now_s=0.5)] == [1, 3, 0]


def test_scheduler_victims_are_strictly_lower_priority():
    s = Scheduler(preemption=True)
    cand = _req(9, 4, 2, priority=1, deadline_s=1.0)
    same = [(0, _req(0, 4, 2, priority=1)), (1, _req(1, 4, 2, priority=1))]
    assert s.pick_victim(same, cand) is None       # equal never parked
    mixed = [(0, _req(0, 4, 2, priority=1)),
             (1, _req(1, 4, 2, priority=0, deadline_s=50.0)),
             (2, _req(2, 4, 2, priority=0, deadline_s=2.0))]
    # lowest priority first, loosest slack within it
    assert s.pick_victim(mixed, cand) == 1
    assert s.pick_victim([], cand) is None


def test_engine_validates_slo_knobs():
    cfg, model, params = _build()
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(model, params, max_len=32, n_slots=2,
                                 prefill_chunk_tokens=8)   # needs paging
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(model, params, max_len=32, n_slots=2,
                                 kv_page_size=8,
                                 scheduler=Scheduler(preemption=True))


# --- Chunked prefill: bimodal trace, injected clock ----------------------

TOKEN_S = 1e-3          # serve-clock cost of one prompt token's prefill
DECODE_CHUNK_S = 0.01   # serve-clock cost of one decode chunk


class TokenClock:
    """A serve clock that moves only when the engine sleeps, prefills
    (``TOKEN_S`` for each prompt token the call computes) or runs a
    decode chunk (``DECODE_CHUNK_S``)."""

    # the jitted body -> which positional argument holds its tokens
    PREFILLS = {"_prefill_slot": 3, "_prefill_chunk": 2, "_extend_slot": 4}

    def __init__(self, engine):
        self.t = 0.0
        for name, arg in self.PREFILLS.items():
            setattr(engine, name, self._timed(
                getattr(engine, name),
                lambda args, i=arg: TOKEN_S * args[i].shape[1]))
        engine._decode_chunk = self._timed(engine._decode_chunk,
                                           lambda args: DECODE_CHUNK_S)

    def _timed(self, fn, cost):
        def run(*args):
            self.t += cost(args)
            return fn(*args)
        return run

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


def test_chunked_prefill_improves_short_ttft_and_keeps_tokens():
    """On a bimodal short/long trace, chunked prefill must strictly
    improve the interactive class's worst TTFT (shorts stop waiting
    out whole long prefills) while emitting bit-identical tokens at
    equal budgets.  Prompt lengths cross chunk boundaries (sub-chunk,
    exact multiple, and non-multiple).  Time is a ``TokenClock``:
    prefill costs in proportion to its tokens and a decode chunk a
    fixed time, so the shorts land *inside* the long's prefill window
    whatever the host's speed."""
    cfg, model, params = _build()
    long_n, mid_n, short_n = 512, 72, 16   # chunk 64: 8x, 1x+8, sub
    kw = dict(max_len=576, n_slots=4, chunk_steps=2, kv_page_size=16,
              kv_pages=150)
    mono = ContinuousBatchingEngine(model, params, **kw)
    chunked = ContinuousBatchingEngine(model, params,
                                       prefill_chunk_tokens=64, **kw)
    t_long = TOKEN_S * long_n        # the long's monolithic prefill

    def trace():
        return ([_req(0, long_n, 4, arrival_s=0.0)]
                + [_req(1 + i, short_n, 4,
                        arrival_s=(0.10 + 0.12 * i) * t_long)
                   for i in range(4)]
                + [_req(5, mid_n, 4, arrival_s=0.6 * t_long)])

    outs, worst_short_ttft = {}, {}
    for name, eng in [("mono", mono), ("chunked", chunked)]:
        clock = TokenClock(eng)
        done = eng.serve(trace(), now=clock.now, sleep=clock.sleep)
        outs[name] = _by_rid(done)
        worst_short_ttft[name] = max(
            r.first_token_s - r.arrival_s for r in done
            if len(r.prompt) == short_n)
    assert outs["chunked"] == outs["mono"]          # token parity
    assert worst_short_ttft["chunked"] < worst_short_ttft["mono"]
    assert chunked.sched_stats["prefill_chunks"] >= 6
    assert chunked.sched_stats["interleaved_chunks"] >= 1


# --- Preemption: park, resume, bit-identical -----------------------------

def test_preempt_park_resume_bit_identical():
    """Under page-pool pressure a late high-priority arrival parks a
    best-effort request (pages evicted, state host-side); the victim
    resumes through the prefix-cache extend path and every request
    still produces exactly the tokens of an uncontended run."""
    cfg, model, params = _build()
    kw = dict(max_len=16, n_slots=3, chunk_steps=2, kv_page_size=4)
    # 12-token prompts + 4 new tokens = 4 pages each; 8 usable pages
    # hold exactly the two best-effort requests -> the short must park
    # one (strictly lower priority) to admit
    eng = ContinuousBatchingEngine(
        model, params, kv_pages=9, prefix_caching=True,
        scheduler=Scheduler(preemption=True), **kw)
    ref = ContinuousBatchingEngine(model, params, kv_pages=33, **kw)

    def trace():
        return [_req(0, 12, 4, arrival_s=0.0, priority=0),
                _req(1, 12, 4, arrival_s=0.0, priority=0),
                _req(2, 4, 4, arrival_s=0.01, priority=1,
                     deadline_s=0.05)]

    for e in (eng, ref):                  # compile off the clock
        e.serve([_req(80, 12, 2, seed_off=500),
                 _req(81, 4, 2, seed_off=500)], honor_arrivals=False)

    t = [0.0]

    def now():
        t[0] += 0.002                     # virtual clock ticks on every
        return t[0]                       # read -> arrivals trigger
                                          # while slots decode

    def sleep(dt):
        t[0] += max(0.0, dt)

    done = eng.serve(trace(), now=now, sleep=sleep)
    assert eng.sched_stats["preemptions"] >= 1
    assert eng.sched_stats["resumes"] >= 1
    assert sorted(r.rid for r in done) == [0, 1, 2]   # qid conservation
    parked = [r for r in done if r.preemptions > 0]
    assert parked and all(r.priority == 0 for r in parked)
    ref_out = _by_rid(ref.serve(trace(), honor_arrivals=False))
    assert _by_rid(done) == ref_out


# --- Disaggregated prefill fleet: priority ordering ----------------------

def test_disagg_prefill_share_serves_priority_first():
    """A worker draining its share must prefill an arrived high-
    priority short before an earlier-arrived best-effort long (no
    preemption of an in-flight prefill; ties stay FIFO)."""
    order = []
    t = [0.0]

    def now():
        return t[0]

    def sleep(dt):
        t[0] += max(0.0, dt)

    worker = SimpleNamespace(
        page_size=4,
        model=SimpleNamespace(cfg=SimpleNamespace(n_kv_heads=2)),
        prefill=lambda r, t0, now_: (order.append(r.rid),
                                     t.__setitem__(0, t[0] + 0.01),
                                     r)[-1])
    decode = SimpleNamespace(paged=True, speculative=False, page_size=4,
                             model=SimpleNamespace(
                                 cfg=SimpleNamespace(n_kv_heads=2)))
    deng = DisaggregatedEngine([worker], decode)
    share = [_req(0, 8, 2, arrival_s=0.0, priority=0),
             _req(1, 8, 2, arrival_s=0.001, priority=0),
             _req(2, 8, 2, arrival_s=0.002, priority=1,
                  deadline_s=0.05)]
    out: queue.Queue = queue.Queue()
    deng._prefill_share(worker, share, out, 0.0, now, sleep, True)
    assert order == [0, 2, 1]


# --- Tail-SLO metrics ----------------------------------------------------

def test_run_server_queue_tail_slos():
    qsl = QuerySampleLibrary(n_samples=16,
                             make_sample=lambda i: {"idx": i})

    def serve(queries):
        recs = []
        for s, arr in queries:
            r = Request(rid=int(s["qid"]), prompt=np.arange(4),
                        max_new_tokens=3, arrival_s=arr)
            # evens answer fast, odds blow the TTFT SLO; everyone
            # decodes at a compliant 10 ms/token cadence
            r.first_token_s = arr + (0.01 if r.rid % 2 == 0 else 0.2)
            r.output = [1, 2, 3]
            r.done_s = r.first_token_s + 0.02
            recs.append(r)
        return recs

    m = run_server_queue(serve, qsl, target_qps=100.0,
                         latency_slo_s=1.0, min_duration_s=0.0,
                         min_queries=10, ttft_slo_s=0.05,
                         tpot_slo_s=0.05)
    assert m.n_tail_miss == 5
    assert m.tail_attainment == pytest.approx(0.5)
    assert not m.slo_met                   # p99 TTFT ~0.2 > 0.05
    loose = run_server_queue(serve, qsl, target_qps=100.0,
                             latency_slo_s=1.0, min_duration_s=0.0,
                             min_queries=10)
    assert np.isnan(loose.tail_attainment)  # no tail SLO set
    assert loose.n_tail_miss == 0 and loose.slo_met


def test_max_sustainable_qps_and_per_joule():
    pts = [(4.0, 0.5), (1.0, 1.0), (2.0, 0.95), (3.0, float("nan"))]
    assert max_sustainable_qps(pts, min_attainment=0.9) == 2.0
    assert max_sustainable_qps(pts, min_attainment=0.99) == 1.0
    assert max_sustainable_qps([], min_attainment=0.9) == 0.0
    assert max_sustainable_qps([(5.0, 0.1)], min_attainment=0.9) == 0.0
    assert qps_at_slo_per_joule(10.0, 100.0) == pytest.approx(0.1)
    assert qps_at_slo_per_joule(10.0, 0.0) == 0.0
