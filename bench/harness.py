"""One run of one cell: set-up, the measured window, the check.

1. Set-up (``setup_s``, from process start to the first due request):
   the weights, made on the device from the seed; the engine; a warm-up
   that serves one request per prompt length of the cell's library, so
   that every program the window runs is compiled or loaded from the
   persistent cache before it opens.
2. The window: the cell's schedule served through the program's
   ``PowerRun`` (queue form), from the first due request to the last
   completion.  With ``trace`` the profiler records it, and the
   per-layer metrics are read from that trace and from counters.
3. The check, after the window: the device's peak memory is read, the
   program's state freed, and a sample of the served requests compared
   with the plain reference (``bench.check``).
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
import types

import jax
import numpy as np

from bench import check, served, spec
from bench import trace as T
from bench import traffic as TR
from bench.compile_log import CompileLog
from bench.peaks import peaks_for

def _p(values, q) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(cell, done, attempted: int, setup_s: float) -> dict:
    """The cell's end-to-end metrics over every request of the window.
    A request that did not finish counts as missing every latency
    limit (an infinite time)."""
    n_missing = attempted - len(done)
    ttft = [r.first_token_s - r.arrival_s for r in done] + \
        [float("inf")] * n_missing
    tpot = [(r.done_s - r.first_token_s) / (len(r.output) - 1)
            for r in done if len(r.output) > 1] + [float("inf")] * n_missing
    span = max(r.done_s for r in done) - min(r.arrival_s for r in done)
    values = {
        "output_tokens_per_s": sum(len(r.output) for r in done) / span,
        "ttft_p90_ms": _p(ttft, 90) * 1e3,
        "tpot_p90_ms": _p(tpot, 90) * 1e3,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_process: float, *,
             pallas_interpret: bool = False, breaker=None,
             keep_trace: str | None = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result's fields.  ``breaker``
    (tests only) alters the engine after it is built; ``keep_trace``
    names a directory to copy the traced run's ``.xplane.pb`` into;
    ``control`` (the control's readings, never a benchmark run) also
    reads the gap of the fp8 control's first token at each position."""
    from repro.harness import PowerRun, TraceServer
    from repro.hw import system_for_device

    cfg, mix = cell.config, cell.traffic
    vocab = cfg["vocab_size"]
    clog = CompileLog().__enter__()
    mc, engine = served.build_engine(cfg, seed, devices,
                                     pallas_interpret=pallas_interpret)
    if breaker is not None:
        breaker(engine)
    warm = TR.warm_up_requests(mix, vocab)
    plan = TR.schedule(mix, seconds, seed, vocab)
    from repro.serving import Request
    engine.serve([Request(rid=p.qid, prompt=p.prompt,
                          max_new_tokens=p.max_new_tokens)
                  for p in warm], honor_arrivals=False)
    n_warm = len(clog.events)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def on_serve(serve):
        if not trace:
            return serve()
        with jax.profiler.TraceAnnotation(T.WINDOW):
            return serve()

    system = (system_for_device(devices[0])
              if devices[0].platform == "tpu" else None)
    sut = served.make_sut(engine, mc, plan, system, on_serve)
    arrivals = np.asarray([p.arrival_s for p in plan])
    scenario = TraceServer(trace=arrivals, latency_slo_s=1e9,
                           ttft_slo_s=mix["slo"]["ttft_s"],
                           tpot_slo_s=mix["slo"]["tpot_s"])
    if trace:
        # JAX's own host events only: the Python tracer would slow the
        # host loop it is meant to explain
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    result = PowerRun(sut, scenario, seed=seed % 2 ** 32).run()
    if trace:
        jax.profiler.stop_trace()
    win = sut.window
    t0 = win.clock.t0
    setup_s = t0 - t_process
    done = sorted(sut.completed, key=lambda r: r.rid)
    in_window = clog.between(t0, win.t_end)
    log(f"[bench] set-up {setup_s:.3f} s; {n_warm} programs obtained in "
        f"set-up ({clog.hits} from the persistent cache); "
        f"{len(in_window)} in the window {[e[1] for e in in_window]}")

    by_qid = {p.qid: p for p in plan}
    bad = [r.rid for r in done
           if len(r.output) != by_qid[r.rid].max_new_tokens
           or not all(0 <= t < vocab for t in r.output)]
    failed = len(plan) - len(done) + len(bad)
    m = result.outcome.server
    n_tokens = sum(len(r.output) for r in done)
    log(f"[bench] {len(done)}/{len(plan)} requests, {n_tokens} tokens, "
        f"window {max(r.done_s for r in done):.3f} s; "
        f"{m.tail_attainment * 100:.1f}% met TTFT <= "
        f"{mix['slo']['ttft_s']} s and TPOT <= {mix['slo']['tpot_s']} s")
    log(f"[bench] modeled J/token (measured time x modeled watts, not a "
        f"metric): {result.summary.energy_j / max(1, n_tokens):.4f}; "
        f"compliance review (frame only): "
        f"{'ACCEPTED' if result.passed else 'REJECTED'}")

    stats = dict(engine.sched_stats)
    out = {"attempted": len(plan), "failed": failed, "stats": stats,
           "prompt_tokens": sum(len(r.prompt) for r in done)}
    n_slots, chunk_steps = engine.n_slots, engine.chunk_steps
    if trace:
        xp = T.find_xplane(trace_dir)
        if keep_trace:
            shutil.copy(xp, keep_trace)
        t_read = time.monotonic()
        tr = T.load(xp)
        ctx = types.SimpleNamespace(
            trace=tr, cfg=cfg, done=done, plan=plan, stats=stats,
            n_slots=n_slots, chunk_steps=chunk_steps, chips=len(devices),
            peaks=peaks_for(devices[0].device_kind)
            if devices[0].platform == "tpu" else None,
            compiles_in_window=len(in_window))
        metrics = {}
        for pm in cell.per_layer:
            v = spec.metric_reader(cell, pm["name"])(ctx)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
        out["metrics"] = metrics
        out["busy_s"] = T.busy_s(tr)
        out["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": T.top_ops(tr),
                            "idle_gaps": T.idle_gaps(tr)}
        log(f"[bench] trace read in {time.monotonic() - t_read:.1f} s: "
            f"{sum(len(e) for e in tr.ops)} device ops")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        out["metrics"] = end_to_end(cell, done, len(plan), setup_s)
    out["memory_peak_bytes"] = _peak_bytes(devices)

    # the check: free the program's state first, the reference after
    seqs = [(np.asarray(r.prompt, np.int32), list(r.output))
            for r in check.sample(done, mix["check_requests"], seed)]
    del engine, sut, result, done
    gc.collect()
    clog.__exit__(None, None, None)
    t_check = time.monotonic()
    with jax.default_device(devices[0]):
        gaps = check.served_gaps(spec.reference_module(cell), cfg, seed,
                                 seqs, cfg["engine"]["max_len"],
                                 control=control)
    gap = max(gaps["served"]) if gaps["served"] else float("inf")
    if control:
        out["control_gap"] = max(gaps["control"])
    limit = cfg["check"]["max_logit_gap"]
    log(f"[bench] reference over {len(seqs)} requests, "
        f"{sum(len(s) for _, s in seqs)} served tokens, in "
        f"{time.monotonic() - t_check:.1f} s; widest gap per request "
        f"{[round(g, 5) for g in gaps['served']]}")
    out["check"] = {"logit_gap": {"value": gap, "limit": limit},
                    "bad_requests": {"value": failed, "limit": 0}}
    out["correct"] = bool(gap <= limit and failed == 0 and seqs)
    return out


def result_line(out: dict, devices: list) -> str:
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "memory_peak_bytes": out["memory_peak_bytes"]}
    if "busy_s" in out:
        dev.update(busy_s=out["busy_s"], window_s=out["window_s"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": dev}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["check"] = out["check"]
    return json.dumps(line)
