"""Serving under measurement, both engines, via ``repro.harness``:

1. Offline scenario — the fixed-batch ``ServeEngine`` behind
   ``ServeEngineSUT`` issues blocking batches (throughput-bound).
2. Server scenario — Poisson arrivals feed the admission queue of the
   slot-based ``ContinuousBatchingEngine`` (``ContinuousBatchingSUT``).
   Finished slots are refilled mid-flight and decoding runs in
   on-device chunks, so the reported TTFT/TPOT reflect real queueing +
   continuous batching.  ``PowerRun`` attributes the Director's power
   samples per request automatically (``per_request_energy_j``).

Each run is one call: ``PowerRun(sut, scenario).run()`` — loadgen,
Director protocol, summarizer, and compliance review included.

  PYTHONPATH=src python examples/serve_power.py
"""
import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.harness import (ContinuousBatchingSUT, Offline, PowerRun,
                           ServeEngineSUT, Server)
from repro.models import build_model
from repro.models.param import init_params
from repro.serving import ContinuousBatchingEngine, Request, ServeEngine


def main():
    cfg = reduce_config(get_config("granite-3-2b"))
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    def make_req(i, arrival_s=0.0, new_tokens=8):
        return Request(rid=i,
                       prompt=jax.random.randint(
                           jax.random.fold_in(key, i), (16,),
                           0, cfg.vocab_size),
                       max_new_tokens=new_tokens, arrival_s=arrival_s)

    # ------------------------------------------------------------------
    # Offline: fixed batches, real CPU timing of one batch per issue
    # ------------------------------------------------------------------
    engine = ServeEngine(model, params, max_len=96, batch_size=4)
    engine.run_batch([make_req(100 + j) for j in range(4)])  # compile

    offline_sut = ServeEngineSUT(
        engine, cfg, name="granite-3-2b-offline",
        make_requests=lambda samples: [make_req(4 * s["idx"] + j)
                                       for s in samples[:1]
                                       for j in range(4)])
    offline = PowerRun(offline_sut, Offline(batch=4, min_duration_s=60.0),
                       seed=0).run()
    res = offline.outcome.result
    print(f"Offline: {res.n_queries} queries, {res.qps:.2f} samples/s, "
          f"p90 {res.p90 * 1e3:.1f} ms, "
          f"{offline.samples_per_joule:.4f} samples/J "
          f"(review {'ACCEPTED' if offline.passed else 'REJECTED'})")

    # ------------------------------------------------------------------
    # Server: Poisson arrivals -> continuous-batching admission queue.
    # Mixed token budgets make the fixed-batch straggler problem real;
    # the slot engine retires short requests early and refills.
    # ------------------------------------------------------------------
    cont = ContinuousBatchingEngine(model, params, max_len=96, n_slots=4,
                                    chunk_steps=4)
    cont.serve([make_req(200, new_tokens=4)],
               honor_arrivals=False)                  # warmup/compile
    server_sut = ContinuousBatchingSUT(
        cont, cfg, name="granite-3-2b-server",
        make_request=lambda i, s, a: make_req(
            i, arrival_s=a, new_tokens=(4, 12, 8)[i % 3]))
    run = PowerRun(server_sut,
                   Server(target_qps=res.qps * 2, latency_slo_s=10.0,
                          mode="queue", min_duration_s=0.5),
                   seed=0)
    r = run.run()
    m = r.outcome.server
    print(f"Server:  {r.outcome.result.qps:.2f} qps, "
          f"{m.tokens_per_s:.1f} tok/s, "
          f"p99 {r.outcome.result.p99 * 1e3:.1f} ms, "
          f"SLO met: {r.outcome.slo_met}")
    print(f"  TTFT p99 {m.ttft_p(99) * 1e3:.1f} ms, "
          f"TPOT mean {m.tpot_mean * 1e3:.2f} ms, "
          f"decode chunks {cont.sched_stats['decode_chunks']}")
    e = np.asarray(list((r.per_request_energy_j or {}).values()))
    print(f"energy: {r.summary.energy_j:.1f} J -> "
          f"{r.samples_per_joule:.4f} samples/J, "
          f"{m.total_tokens / max(r.summary.energy_j, 1e-9):.3f} tok/J, "
          f"per-request mean {e.mean():.2f} J")
    # the meter stack's per-domain split: DC rails vs the wall boundary
    print("per-domain: " + ", ".join(
        f"{k}={v:.1f} J" for k, v in sorted(r.per_domain_energy_j.items())))
    print(r.report.render())


if __name__ == "__main__":
    main()
