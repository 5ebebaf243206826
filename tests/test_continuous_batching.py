"""Continuous-batching engine: greedy parity with the fixed-batch path,
mid-flight slot refill isolation, chunked host-sync accounting, and the
queue-driven Server loadgen mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.loadgen import (LoadgenResult, poisson_arrivals,
                                run_server_queue, QuerySampleLibrary)
from repro.models import build_model
from repro.models.param import init_params
from repro.serving import (ContinuousBatchingEngine, Request, ServeEngine,
                           attribute_request_energy)


def _build(arch="qwen3-1.7b", **overrides):
    cfg = reduce_config(get_config(arch))
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    return cfg, model, params


def _mixed_requests(cfg, budgets, prompt_len=8):
    return [Request(rid=i, prompt=np.arange(prompt_len) + 3 * i,
                    max_new_tokens=b) for i, b in enumerate(budgets)]


def _fixed_reference(model, params, requests, batch, max_len):
    """Old fixed-batch greedy outputs, batch groups in request order."""
    eng = ServeEngine(model, params, max_len=max_len, batch_size=batch)
    want = {}
    for i in range(0, len(requests), batch):
        group = [Request(rid=r.rid, prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens)
                 for r in requests[i:i + batch]]
        for r in eng.run_batch(group):
            want[r.rid] = r.output
    return want


def test_continuous_matches_fixed_batch_greedy():
    """Token-for-token parity incl. mid-flight refill (4 reqs, 2 slots),
    with strictly fewer host syncs than decoded tokens."""
    cfg, model, params = _build()
    budgets = [4, 7, 0, 6]          # incl. zero-budget edge: no tokens
    reqs = _mixed_requests(cfg, budgets)
    want = _fixed_reference(model, params, reqs, batch=2, max_len=48)

    eng = ContinuousBatchingEngine(model, params, max_len=48, n_slots=2,
                                   chunk_steps=3)
    done = eng.serve(_mixed_requests(cfg, budgets), honor_arrivals=False)
    got = {r.rid: r.output for r in done}
    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], rid
    # zero per-token host syncs inside a chunk: the only decode-loop
    # syncs are the once-per-chunk buffer fetches
    decode_tokens = sum(max(0, b - 1) for b in budgets)
    assert eng.sched_stats["decode_chunks"] < decode_tokens
    for r in done:
        assert r.first_token_s is not None and r.done_s is not None


def test_continuous_matches_fixed_with_ragged_pallas_kernel():
    """Same parity with decode attention routed through the ragged
    split-KV Pallas kernel (interpret=True on CPU) on both engines."""
    cfg, model, params = _build(use_pallas=True, pallas_interpret=True)
    budgets = [3, 5, 4]
    reqs = _mixed_requests(cfg, budgets)
    want = _fixed_reference(model, params, reqs, batch=2, max_len=32)

    eng = ContinuousBatchingEngine(model, params, max_len=32, n_slots=2,
                                   chunk_steps=2)
    done = eng.serve(_mixed_requests(cfg, budgets), honor_arrivals=False)
    got = {r.rid: r.output for r in done}
    for rid in want:
        assert got[rid] == want[rid], rid


def test_slot_refill_preserves_other_slots():
    """Prefilling into one slot must not disturb any other slot's KV
    rows, position, seed token, or budget."""
    cfg, model, params = _build()
    eng = ContinuousBatchingEngine(model, params, max_len=48, n_slots=3,
                                   chunk_steps=2)
    p0 = jnp.asarray(np.arange(8))[None].astype(jnp.int32)
    p1 = jnp.asarray(np.arange(6) + 40)[None].astype(jnp.int32)
    state, _ = eng._prefill_slot(eng.params, eng.draft_params, eng.state,
                                 p0, jnp.asarray(0, jnp.int32),
                                 jnp.asarray(5, jnp.int32))

    def snap_slot(state, b):
        rows = jax.tree.map(lambda a: np.asarray(a[:, b]),
                            state["cache"]["layers"])
        return (rows, int(state["cache"]["pos"][b]),
                int(state["tok"][b]), int(state["remaining"][b]))

    before = snap_slot(state, 0)
    # refill a *different* slot mid-flight (donated state: snapshot
    # above copies to host first)
    state, _ = eng._prefill_slot(eng.params, eng.draft_params, state,
                                 p1, jnp.asarray(1, jnp.int32),
                                 jnp.asarray(4, jnp.int32))
    after = snap_slot(state, 0)
    jax.tree.map(np.testing.assert_array_equal, before[0], after[0])
    assert before[1:] == after[1:]
    # and slot 1 actually took the new prompt
    assert int(state["cache"]["pos"][1]) == p1.shape[1]
    assert int(state["remaining"][1]) == 3


def test_run_server_queue_metrics():
    """Queue-driven Server mode derives latency/TTFT/TPOT/token stats
    from the request records the engine returns."""
    class _Rec:
        def __init__(self, a, f, d, n):
            self.arrival_s, self.first_token_s, self.done_s = a, f, d
            self.output = list(range(n))

    def serve(arrivals):
        return [_Rec(a, a + 0.01, a + 0.01 + 0.002 * 4, 5)
                for _, a in arrivals]

    qsl = QuerySampleLibrary(8, lambda i: {"idx": i})
    m = run_server_queue(serve, qsl, target_qps=100.0, latency_slo_s=0.1,
                         min_duration_s=0.05, seed=3)
    assert m.slo_met
    assert m.total_tokens == m.result.n_queries * 5
    assert m.tokens_per_s > 0
    np.testing.assert_allclose(m.ttft_s, 0.01, atol=1e-9)
    np.testing.assert_allclose(m.tpot_s, 0.002, atol=1e-9)


def test_poisson_arrivals_deterministic_and_min_queries():
    a1 = poisson_arrivals(10.0, min_duration_s=0.0, seed=5, min_queries=20)
    a2 = poisson_arrivals(10.0, min_duration_s=0.0, seed=5, min_queries=20)
    np.testing.assert_array_equal(a1, a2)
    assert len(a1) == 20 and np.all(np.diff(a1) > 0)


def test_percentile_sorted_once_and_empty_nan():
    lat = np.asarray([0.5, 0.1, 0.9, 0.3])
    res = LoadgenResult("Server", 4, 1.0, lat, qps=4.0,
                        min_duration_met=True)
    assert res._sorted_latencies is res._sorted_latencies  # cached
    for p in (50, 90, 99):
        np.testing.assert_allclose(res.percentile(p),
                                   np.percentile(lat, p))
    empty = LoadgenResult("Server", 0, 0.0, np.asarray([]), qps=0.0,
                          min_duration_met=False)
    assert np.isnan(empty.percentile(99))


def test_attribute_request_energy_splits_overlap():
    r0 = Request(rid=0, prompt=[1], arrival_s=0.0)
    r0.done_s, r0.first_token_s, r0.output = 2.0, 0.5, [1, 2]
    r1 = Request(rid=1, prompt=[1], arrival_s=1.0)
    r1.done_s, r1.first_token_s, r1.output = 2.0, 1.5, [3]
    t = np.asarray([0.0, 1.0, 2.0, 3.0])
    w = np.asarray([10.0, 10.0, 10.0, 10.0])
    per = attribute_request_energy([r0, r1], t, w)
    # [0,1): r0 alone (10 J); [1,2): split (5 J each); [2,3): idle
    np.testing.assert_allclose(per[0], 15.0)
    np.testing.assert_allclose(per[1], 5.0)
    assert r0.energy_j == pytest.approx(15.0)
