"""The serve loop's instrumentation: the admission stamp on an injected
clock, the host spans it writes into a profiler trace, and served
tokens that do not depend on a profiler running."""
import collections
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import build_model
from repro.models.param import init_params
from repro.serving import ContinuousBatchingEngine, Request, Scheduler

PREFILL_S, CHUNK_S = 0.25, 1.0


@pytest.fixture(scope="module")
def model_params():
    cfg = reduce_config(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    return model, init_params(model.param_defs(), jax.random.PRNGKey(0))


def _req(rid, n_prompt, budget, arrival_s=0.0, priority=0):
    rng = np.random.default_rng(2_000 + rid)
    return Request(rid=rid, prompt=rng.integers(0, 512, n_prompt),
                   max_new_tokens=budget, arrival_s=arrival_s,
                   priority=priority)


class Clock:
    """A serve clock that moves only when the engine sleeps, prefills
    a prompt (``PREFILL_S``) or runs a decode chunk (``CHUNK_S``)."""

    def __init__(self, engine):
        self.t = 0.0
        for name, dt in (("_prefill_slot", PREFILL_S),
                         ("_decode_chunk", CHUNK_S)):
            setattr(engine, name, self._timed(getattr(engine, name), dt))

    def _timed(self, fn, dt):
        def run(*args):
            self.t += dt
            return fn(*args)
        return run

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


def _serve(engine, requests):
    clock = Clock(engine)
    done = {r.rid: r for r in engine.serve(requests, now=clock.now,
                                           sleep=clock.sleep)}
    for r in done.values():
        assert r.arrival_s <= r.admitted_s <= r.first_token_s, r.rid
    return done


def test_a_request_due_during_a_chunk_is_admitted_when_it_ends(
        model_params):
    eng = ContinuousBatchingEngine(*model_params, max_len=16, n_slots=2,
                                   chunk_steps=2, kv_page_size=4)
    done = _serve(eng, [_req(0, 8, 6), _req(1, 4, 3, arrival_s=0.5),
                        _req(2, 4, 2, arrival_s=5.0)])
    # 0 prefills in [0, 0.25), its chunk runs [0.25, 1.25); 1 arrives in
    # it and waits for its end; 2 arrives after an idle sleep
    assert [(done[i].admitted_s, done[i].first_token_s)
            for i in range(3)] == [(0.0, 0.25), (1.25, 1.5), (5.0, 5.25)]


def test_a_deferred_request_is_stamped_when_finally_admitted(
        model_params):
    # four usable pages: 0 holds them all until it retires
    eng = ContinuousBatchingEngine(*model_params, max_len=16, n_slots=2,
                                   chunk_steps=2, kv_page_size=4,
                                   kv_pages=5)
    done = _serve(eng, [_req(0, 8, 8), _req(1, 4, 3, arrival_s=0.5)])
    # deferred at 1.25, 2.25 and 3.25; 0 retires at the end of its
    # fourth chunk
    assert done[0].done_s == 4.25
    assert (done[1].admitted_s, done[1].first_token_s) == (4.25, 4.5)


def _preempting(model_params):
    """Two best-effort requests fill the pool; a late high-priority one
    parks one of them, which resumes through the prefix cache."""
    eng = ContinuousBatchingEngine(
        *model_params, max_len=16, n_slots=3, chunk_steps=2,
        kv_page_size=4, kv_pages=9, prefix_caching=True,
        scheduler=Scheduler(preemption=True))
    reqs = [_req(0, 12, 4), _req(1, 12, 4),
            _req(2, 4, 4, arrival_s=0.01, priority=1)]
    return eng, reqs


def test_a_resumed_request_keeps_its_first_stamp(model_params):
    eng, reqs = _preempting(model_params)
    done = _serve(eng, reqs)
    assert eng.sched_stats["resumes"] >= 1
    parked = [r for r in done.values() if r.preemptions]
    assert parked
    # both best-effort requests were admitted in the first iteration,
    # 2 after the first chunk; the victim resumed later still
    assert {done[0].admitted_s, done[1].admitted_s} == {0.0, PREFILL_S}
    assert done[2].admitted_s == 2 * PREFILL_S + CHUNK_S
    for r in parked:
        assert r.first_token_s == r.admitted_s + PREFILL_S


def _engine_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    s = float(e.start_ns)
                    out[e.name].append((s, s + float(e.duration_ns),
                                        dict(e.stats)))
    return out


def test_spans_in_a_profiler_trace_and_tokens_unchanged(model_params,
                                                        tmp_path):
    eng, reqs = _preempting(model_params)
    plain = _serve(eng, reqs)
    eng, reqs = _preempting(model_params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced = _serve(eng, reqs)
    finally:
        jax.profiler.stop_trace()
    assert {r: t.output for r, t in traced.items()} == \
        {r: p.output for r, p in plain.items()}

    spans = _engine_spans(tmp_path)
    stats = eng.sched_stats
    assert stats["resumes"] >= 1
    admits = spans["engine.admit"]
    assert len(admits) == len(reqs) + stats["resumes"]
    assert sorted(a[2]["rid"] for a in admits) == sorted(
        [r.rid for r in reqs] + [r.rid for r in traced.values()
                                 if r.preemptions])
    assert len(spans["engine.decode_sync"]) == stats["decode_chunks"]
    assert len(spans["engine.decode"]) == stats["decode_chunks"]
    assert len(spans["engine.first_token"]) == len(admits)
    for s, e, kw in spans["engine.first_token"]:
        assert any(a[0] <= s and e <= a[1] and a[2]["rid"] == kw["rid"]
                   for a in admits)
    steps = spans["engine.step"]
    for name, evs in spans.items():
        if name != "engine.step":
            assert all(any(t[0] <= s and e <= t[1] for t in steps)
                       for s, e, _ in evs), name
