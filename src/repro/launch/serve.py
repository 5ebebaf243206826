"""Serving launcher: ``--arch <id>``, loadgen scenario, Director-
measured Samples/Joule through the ``repro.harness`` API.

Two engines, four scenarios, one call path: the engine is wrapped in a
SUT adapter, the scenario is a config dataclass, and
``PowerRun(sut, scenario).run()`` does loadgen + Director protocol +
summarizer + compliance in one shot.

- ``--engine fixed``: the synchronous fixed-batch ``ServeEngine``
  (``ServeEngineSUT``) — single-stream, multi-stream, offline, or the
  synchronous server form.
- ``--engine continuous``: the slot-based ``ContinuousBatchingEngine``
  (``ContinuousBatchingSUT``) under ``--scenario server`` — the
  Poisson arrival schedule feeds the engine's admission queue
  asynchronously, the Director drives the SUT's multi-channel meter
  stack (utilization-shaped accelerator/dram/host rails under one
  PSU-derived wall), and every request is attributed its share of the
  measured Joules (TTFT/TPOT/energy per request, tokens/s and
  tokens/J, per-domain split).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduce --scenario server --engine continuous --qps 8 \
      --min-duration 2

Speculative decoding (``--speculative --draft <config> --k 4``): a
small draft model proposes k tokens per slot and the target verifies
the window in one multi-token forward — greedy output is
token-identical to plain decode, and the run reports the measured
acceptance rate.  ``--draft truncate`` (default) needs no second
checkpoint: it reuses the target's first ``--draft-layers`` blocks
(LayerSkip-style early exit).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --reduce --scenario server --engine continuous --speculative \
      --k 4 --qps 8 --min-duration 2

Scale axis (the paper's µW -> MW sweep): ``--tp K`` shards the
continuous engine over a K-way tensor-parallel mesh
(``ShardedContinuousBatchingEngine`` + ``ShardedSUT``, one accelerator
channel per shard summed under one wall), ``--replicas R`` runs R
independent engines behind one admission queue (``ReplicatedSUT``;
the fleet boundary is a PDU domain aggregating the replica wall
feeds).  Without accelerators, run on virtual host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --reduce --scenario server --engine continuous --tp 4 \
      --qps 8 --min-duration 2
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import (for_platform, get_config, list_archs,
                           reduce_config)
from repro.core.loadgen import qid_of
from repro.harness import (ContinuousBatchingSUT, MultiStream, Offline,
                           PowerRun, ReplicatedSUT, ServeEngineSUT,
                           Server, ShardedSUT, SingleStream)
from repro.hw import system_for_device
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_tp_mesh
from repro.models import build_model
from repro.models.param import init_params
from repro.serving import (ContinuousBatchingEngine, Request, Scheduler,
                           ServeEngine, ShardedContinuousBatchingEngine,
                           tp_param_shardings, truncate_draft)


def _make_request(key, cfg, i, arrival_s=0.0, new_tokens=8):
    return Request(
        rid=i,
        prompt=jax.random.randint(jax.random.fold_in(key, i), (16,), 0,
                                  cfg.vocab_size),
        max_new_tokens=new_tokens, arrival_s=arrival_s)


def _scenario_for(args):
    if args.scenario == "offline":
        return Offline(batch=args.batch, min_duration_s=args.min_duration)
    if args.scenario == "server":
        return Server(target_qps=args.qps, latency_slo_s=10.0,
                      mode="queue" if args.engine == "continuous"
                      else "sync", min_duration_s=args.min_duration)
    if args.scenario == "multi-stream":
        return MultiStream(n_streams=args.streams,
                           min_duration_s=args.min_duration)
    return SingleStream(min_duration_s=args.min_duration)


def _build_draft(args, cfg, model, params):
    """(draft_model, draft_params, draft_cfg) for ``--speculative``.

    ``--draft truncate`` builds the LayerSkip-style self-draft (the
    target's first ``--draft-layers`` blocks, shared embeddings/head);
    any arch name builds that config (reduced alongside ``--reduce``)
    with fresh weights — vocabularies must match.
    """
    if args.draft == "truncate":
        dmodel, dparams = truncate_draft(model, params,
                                         n_layers=args.draft_layers)
        return dmodel, dparams, dmodel.cfg
    dcfg = get_config(args.draft)
    if args.reduce:
        dcfg = reduce_config(dcfg)
    dcfg = for_platform(dcfg)
    if dcfg.vocab_size != cfg.vocab_size:
        raise SystemExit(
            f"--draft {args.draft}: vocab {dcfg.vocab_size} != target "
            f"vocab {cfg.vocab_size} (draft and target must share the "
            f"tokenizer)")
    dmodel = build_model(dcfg)
    dparams = init_params(dmodel.param_defs(), jax.random.PRNGKey(2))
    return dmodel, dparams, dcfg


def _build_continuous_engine(args, model, params, spec_kw, mesh):
    paged_kw = {}
    if args.kv_page_size:
        paged_kw = dict(kv_page_size=args.kv_page_size,
                        prefix_caching=args.prefix_cache)
    if args.prefill_chunk:
        paged_kw["prefill_chunk_tokens"] = args.prefill_chunk
    if args.preemption:
        paged_kw["scheduler"] = Scheduler(preemption=True)
    if args.tp > 1:
        return ShardedContinuousBatchingEngine(
            model, params, mesh=mesh, max_len=args.max_len,
            n_slots=args.slots, chunk_steps=args.chunk_steps,
            **paged_kw, **spec_kw)
    return ContinuousBatchingEngine(
        model, params, max_len=args.max_len, n_slots=args.slots,
        chunk_steps=args.chunk_steps, **paged_kw, **spec_kw)


def _serve_continuous(args, cfg, model, params, system, mesh):
    key = jax.random.PRNGKey(1)

    spec_kw, draft_cfg = {}, None
    if args.speculative:
        dmodel, dparams, draft_cfg = _build_draft(args, cfg, model,
                                                  params)
        spec_kw = dict(draft_model=dmodel, draft_params=dparams,
                       spec_k=args.k)

    def make_request(i, s, a):
        # rid from the loadgen query id, not the per-replica enumerate
        # index: replicas each see a share of the queue, and energy
        # attribution needs fleet-unique request ids
        return _make_request(key, cfg, qid_of(s, i), arrival_s=a,
                             new_tokens=args.new_tokens)

    def one_sut(idx):
        engine = _build_continuous_engine(args, model, params, spec_kw,
                                          mesh)
        # warmup/compile: one prefill + one chunk outside the measurement
        engine.serve([_make_request(key, cfg, 10 ** 6,
                                    new_tokens=args.new_tokens)],
                     honor_arrivals=False)
        name = f"{args.arch}-continuous" + (
            f"-k{args.k}" if args.speculative else "") + (
            f"-r{idx}" if args.replicas > 1 else "")
        if args.tp > 1:
            return ShardedSUT(engine, cfg, name=f"{name}-tp{args.tp}",
                              make_request=make_request, system=system,
                              draft=draft_cfg), engine
        return ContinuousBatchingSUT(engine, cfg, name=name,
                                     make_request=make_request,
                                     system=system,
                                     draft=draft_cfg), engine

    pairs = [one_sut(i) for i in range(args.replicas)]
    engines = [e for _, e in pairs]
    if args.replicas > 1:
        sut = ReplicatedSUT([s for s, _ in pairs],
                            name=f"{args.arch}-x{args.replicas}")
    else:
        sut = pairs[0][0]
    run = PowerRun(sut, _scenario_for(args), seed=0)
    r = run.run()

    m = r.outcome.server
    # queue wait: arrival to the admission that took the request into a
    # slot; TTFT = queue wait + admission (prefill and first token)
    waits = [q.admitted_s - q.arrival_s
             for q in sut.completed_requests() or []
             if q.admitted_s is not None]
    qw = (f", queue wait p50/p90: {np.percentile(waits, 50) * 1e3:.1f}/"
          f"{np.percentile(waits, 90) * 1e3:.1f} ms" if waits else "")
    print(r.render())
    print(f"  TTFT p50/p99: {m.ttft_p(50) * 1e3:.1f}/"
          f"{m.ttft_p(99) * 1e3:.1f} ms{qw}, "
          f"TPOT mean: {m.tpot_mean * 1e3:.2f} ms, "
          f"decode chunks: "
          f"{sum(e.sched_stats['decode_chunks'] for e in engines)} "
          f"({m.total_tokens} tokens)")
    print(f"  {m.total_tokens / max(r.summary.energy_j, 1e-9):.3f} tok/J"
          + (f" across tp={args.tp}" if args.tp > 1 else "")
          + (f" x {args.replicas} replicas" if args.replicas > 1 else "")
          + f" (modeled: measured time x {system.chip.name} system "
            f"watts)")
    if args.speculative:
        acc = sum(e.spec_stats["accepted"] for e in engines) / max(
            1, sum(e.spec_stats["proposed"] for e in engines))
        print(f"  speculative k={args.k} "
              f"(draft {draft_cfg.name}): acceptance {acc:.2f}, "
              f"{sum(e.spec_stats['rounds'] for e in engines)} verified "
              f"slot-rounds")
    # guard on engine state, not the CLI flag: only engines actually
    # running the radix cache have meaningful prefix stats
    if any(getattr(e, "prefix_caching", False) for e in engines):
        lookups = sum(e.prefix_stats["lookups"] for e in engines)
        hits = sum(e.prefix_stats["hits"] for e in engines)
        cached = sum(e.prefix_stats["cached_tokens"] for e in engines)
        evicted = sum(e.prefix_stats["evicted_pages"] for e in engines)
        peak = max(e.page_pool.peak_used for e in engines)
        print(f"  prefix cache: {hits}/{lookups} hits, {cached} prompt "
              f"tokens served from cache, {evicted} pages evicted, "
              f"peak {peak} pages "
              f"(page size {args.kv_page_size})")
    sched = [getattr(e, "sched_stats", None) or {} for e in engines]
    if any(v for s in sched for v in s.values()):
        pre = sum(s.get("preemptions", 0) for s in sched)
        res = sum(s.get("resumes", 0) for s in sched)
        chunks = sum(s.get("prefill_chunks", 0) for s in sched)
        inter = sum(s.get("interleaved_chunks", 0) for s in sched)
        print(f"  scheduler: {pre} preemptions, {res} resumes, "
              f"{chunks} prefill chunks "
              f"({inter / max(1, chunks):.0%} interleaved with decode)")
    e = np.asarray(list((r.per_request_energy_j or {}).values()))
    if e.size:
        print(f"  per-request energy: mean {e.mean():.2f} J, "
              f"p90 {np.percentile(e, 90):.2f} J")
    dom = r.per_domain_energy_j
    if len(dom) > 1:
        split = "; ".join(f"{k}={v:.2f}J" for k, v in sorted(dom.items()))
        print(f"  per-domain energy: {split}")
    if args.replicas > 1:
        per_rep = [dom.get(f"r{i}/wall", 0.0)
                   for i in range(args.replicas)]
        split = "/".join(f"{x:.2f}" for x in per_rep)
        print(f"  per-replica wall energy: {split} J "
              f"(sum {sum(per_rep):.2f} J vs fleet PDU "
              f"{r.summary.energy_j:.2f} J)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--scenario", default="offline",
                    choices=["offline", "server", "single-stream",
                             "multi-stream"])
    ap.add_argument("--engine", default="fixed",
                    choices=["fixed", "continuous"])
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--streams", type=int, default=4,
                    help="samples per MultiStream burst")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk-steps", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (sharded engine; needs "
                         "tp devices — virtual on CPU via XLA_FLAGS)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="independent engine replicas behind one "
                         "admission queue (fleet power summed)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: draft k tokens with a "
                         "small model, verify in one target forward")
    ap.add_argument("--draft", default="truncate",
                    help="draft model: 'truncate' (the target's first "
                         "--draft-layers blocks, shared embed/head) or "
                         "an arch name with a matching vocab")
    ap.add_argument("--draft-layers", type=int, default=2,
                    help="layers kept by --draft truncate")
    ap.add_argument("--k", type=int, default=4,
                    help="draft tokens per verify round")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0 = the "
                         "contiguous per-slot layout); must divide "
                         "--max-len")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix caching over the KV pages: "
                         "shared prompt prefixes skip their prefill "
                         "(needs --kv-page-size)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="SLO-aware chunked prefill: tokens per "
                         "prefill chunk, interleaved with decode "
                         "(needs --kv-page-size; 0 = whole-prompt "
                         "prefill at admission)")
    ap.add_argument("--preemption", action="store_true",
                    help="priority scheduler with preemption: a "
                         "high-priority arrival under page-pool "
                         "pressure parks a best-effort request "
                         "(needs --prefix-cache)")
    ap.add_argument("--qps", type=float, default=4.0)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--min-duration", type=float, default=60.0)
    args = ap.parse_args(argv)

    if args.engine == "continuous" and args.scenario != "server":
        ap.error("--engine continuous currently drives the server "
                 "scenario (its admission queue is the point); use "
                 "--scenario server")
    if (args.tp > 1 or args.replicas > 1) and args.engine != "continuous":
        ap.error("--tp/--replicas shard the continuous engine; add "
                 "--engine continuous")
    if args.speculative and args.engine != "continuous":
        ap.error("--speculative is a continuous-engine decode mode; "
                 "add --engine continuous")
    if args.kv_page_size and args.engine != "continuous":
        ap.error("--kv-page-size pages the continuous engine's KV "
                 "cache; add --engine continuous")
    if args.prefix_cache and not args.kv_page_size:
        ap.error("--prefix-cache needs --kv-page-size (prefix pages "
                 "are shared at page granularity)")
    if args.prefill_chunk and not args.kv_page_size:
        ap.error("--prefill-chunk needs --kv-page-size (chunks write "
                 "through the paged verify path)")
    if args.preemption and not args.prefix_cache:
        ap.error("--preemption needs --prefix-cache (a parked "
                 "request's KV pages survive as cache entries until "
                 "resume)")

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    cfg = for_platform(cfg)
    model = build_model(cfg)
    system = system_for_device(jax.devices()[0])
    # a tensor-parallel model is created straight into its shards
    mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
    params = init_params(model.param_defs(), jax.random.PRNGKey(0),
                         tp_param_shardings(model, mesh) if mesh else None)

    if args.engine == "continuous":
        _serve_continuous(args, cfg, model, params, system, mesh)
        return

    batch_cap = max(args.batch, args.streams
                    if args.scenario == "multi-stream" else 1)
    engine = ServeEngine(model, params, max_len=args.max_len,
                         batch_size=batch_cap)
    key = jax.random.PRNGKey(1)

    def make_requests(samples):
        return [_make_request(key, cfg, s["idx"],
                              new_tokens=args.new_tokens)
                for s in samples]

    # warm the jit cache with the batch shape the scenario will issue
    # (run_batch compiles per batch size)
    warm_n = {"offline": args.batch,
              "multi-stream": args.streams}.get(args.scenario, 1)
    engine.run_batch(make_requests([{"idx": j} for j in range(warm_n)]))
    sut = ServeEngineSUT(engine, cfg, name=f"{args.arch}-fixed",
                         make_requests=make_requests, system=system)
    r = PowerRun(sut, _scenario_for(args), seed=0).run()
    print(r.render())


if __name__ == "__main__":
    main()
