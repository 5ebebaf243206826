#!/usr/bin/env python3
"""Bring-up smoke of the served path on the TPU.

One chip (the default): qwen3-1.7b at its published width, with seeded
random weights, served through the objects a user drives — a
``ContinuousBatchingEngine`` with paged KV (page 128) behind a
``ContinuousBatchingSUT``, measured by ``PowerRun`` under the ``Server``
scenario.  It checks that

- every request finishes with its full token budget;
- the probed decode logits are finite, and the Pallas kernels' logits
  agree with the jnp attention path on the chip within
  ``PALLAS_VS_JNP_RTOL``;
- the compiled decode-chunk program holds the Pallas kernels
  (``tpu_custom_call``), so nothing on the chip falls back to jnp or
  the interpreter.

Four chips (``--tp4``), and only that: yi-9b at its published width on
``ShardedContinuousBatchingEngine`` with TP=4 and paged KV serves a few
requests through ``ShardedSUT``; then, at yi-9b widths with the depth
cut to what one chip holds, one decode step's logits at TP=4 are
compared with the unsharded engine on chip 0 within
``TP4_VS_ONE_CHIP_RTOL``.

    python chip_smoke.py          # one chip
    python chip_smoke.py --tp4    # four chips

JAX must find a TPU; there is no CPU fallback.  Any failed check exits
non-zero.  The last line of stdout is one JSON object naming the device
as JAX reports it.  Throughput and latency printed here are smoke from
one short run, not benchmark numbers; J/token is modeled (measured time
times the ``repro.hw`` system watts of the device kind).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import for_platform, get_config  # noqa: E402
from repro.core.loadgen import qid_of  # noqa: E402
from repro.harness import (ContinuousBatchingSUT, PowerRun,  # noqa: E402
                           Server, ShardedSUT)
from repro.hw import system_for_device  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_tp_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.param import init_params, param_count  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, Request,  # noqa: E402
                           ShardedContinuousBatchingEngine,
                           tp_param_shardings)

# one chip: qwen3-1.7b served
ARCH = "qwen3-1.7b"
SLOTS, MAX_LEN, PAGE, CHUNK_STEPS = 8, 2048, 128, 8
PROMPT_LENS = (128, 512, 1024)
N_REQUESTS, NEW_TOKENS, QPS = 16, 64, 8.0
# relative L2 error of one decode step's logits, kernel vs jnp path:
# both attend in f32 and round to bf16 at different points, and the
# differences carry through every layer (bf16 models of these widths
# differ by ~1e-2 between such paths on CPU); attending to the wrong
# rows or pages is an O(1) error
PALLAS_VS_JNP_RTOL = 5e-2

# four chips: yi-9b at TP=4
TP4_ARCH = "yi-9b"
TP4_PROMPT_LENS = (512,)
TP4_REQUESTS, TP4_NEW_TOKENS, TP4_QPS = 8, 32, 4.0
# the one-chip reference keeps yi-9b's widths and a third of its depth
TP4_REFERENCE_LAYERS = 16
# bf16 partial sums reduced across shards round differently from the
# one-chip matmuls (1.4e-2 at d_model 1024 and 8 layers on CPU)
TP4_VS_ONE_CHIP_RTOL = 5e-2


class CompileLog:
    """XLA compilations seen through JAX's monitoring events: seconds
    spent (a persistent-cache hit costs only its read), programs, and
    persistent-cache hits."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds, self.programs, self.hits = 0.0, 0, 0

    def _duration(self, name, secs, **_):
        if name == self._COMPILE:
            self.seconds += secs
            self.programs += 1

    def _event(self, name, **_):
        if name == self._HIT:
            self.hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.programs, self.hits


def require_tpu(count: int = 1) -> list:
    """The TPU devices, or exit non-zero naming what JAX found."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found {len(devs)} "
                 f"{devs[0].platform} device(s) ({devs[0].device_kind}); "
                 f"this smoke runs on the chip only")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX found "
                 f"{len(devs)}")
    return devs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def make_prompts(vocab: int, n: int, lens, seed: int) -> list:
    """``n`` seeded prompts cycling through the lengths ``lens``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, lens[i % len(lens)], dtype=np.int32)
            for i in range(n)]


def engine_kw(slots=SLOTS, max_len=MAX_LEN, page=PAGE,
              chunk_steps=CHUNK_STEPS) -> dict:
    return dict(n_slots=slots, max_len=max_len, kv_page_size=page,
                chunk_steps=chunk_steps)


def warm_up(engine, prompts, new_tokens: int) -> float:
    """Compile every program the window uses: one request per prompt
    length, drained at once.  Returns wall seconds."""
    reqs = [Request(rid=10 ** 6 + i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    engine.serve(reqs, honor_arrivals=False)
    return time.perf_counter() - t0


def serve_measured(sut_cls, engine, cfg, system, prompts, new_tokens: int,
                   qps: float, name: str):
    """Serve ``prompts`` (request ``q`` asks ``prompts[q]``) through
    ``PowerRun`` under ``Server``; check every request came back with
    its full budget of in-vocabulary tokens.  Returns the
    ``SubmissionResult``."""

    def make_request(i, sample, arrival_s):
        q = qid_of(sample, i)
        return Request(rid=q, prompt=prompts[q % len(prompts)],
                       max_new_tokens=new_tokens, arrival_s=arrival_s)

    sut = sut_cls(engine, cfg, name=name, make_request=make_request,
                  system=system)
    # the run is as long as its len(prompts) arrivals take
    scenario = Server(target_qps=qps, mode="queue", min_queries=len(prompts),
                      min_duration_s=0.0, latency_slo_s=60.0)
    result = PowerRun(sut, scenario, seed=0).run()
    done = sut.completed
    check(len(done) == len(prompts),
          f"{len(done)}/{len(prompts)} requests completed")
    short = [r.rid for r in done if len(r.output) != new_tokens]
    check(not short, f"requests {short} ended short of {new_tokens} tokens")
    toks = np.concatenate([np.asarray(r.output) for r in done])
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          "a served token is outside the vocabulary")
    return result


def report_serving(result, system, tag: str) -> None:
    m = result.outcome.server
    print(f"{tag} served {len(m.ttft_s)} requests, {m.total_tokens} "
          f"tokens; TTFT p50/p99 {m.ttft_p(50) * 1e3:.1f}/"
          f"{m.ttft_p(99) * 1e3:.1f} ms")
    print(f"{tag} smoke (one short run, not a benchmark): "
          f"{m.tokens_per_s:.1f} tokens/s")
    print(f"{tag} modeled J/token (measured time x {system.chip.name} "
          f"system watts): "
          f"{result.summary.energy_j / max(1, m.total_tokens):.4f}")
    verdict = "ACCEPTED" if result.passed else "REJECTED " + "; ".join(
        c.rule for c in result.report.failures())
    print(f"{tag} compliance review: {verdict}")


def rel_error(a, b, vocab: int) -> float:
    """Relative L2 error of logits ``a`` against ``b`` over the real
    vocabulary (float32)."""
    a = np.asarray(a, np.float32)[:vocab]
    b = np.asarray(b, np.float32)[:vocab]
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def probe_logits(engine, prompts) -> list:
    """First-decode-step logits after each prompt; all must be finite."""
    rows = [np.asarray(engine.first_step_logits(p)) for p in prompts]
    for p, row in zip(prompts, rows):
        check(bool(np.isfinite(row).all()),
              f"non-finite logits after a {len(p)}-token prompt")
    return rows


def kernel_parity(engine, prompt) -> float:
    """Relative error of one decode step's logits: the engine's own
    attention path against the jnp path on the same weights."""
    cfg = engine.model.cfg
    ref_cfg = dataclasses.replace(cfg, use_pallas=False,
                                  pallas_interpret=False)
    ref = ContinuousBatchingEngine(
        build_model(ref_cfg), engine.params,
        **engine_kw(engine.n_slots, engine.max_len, engine.page_size,
                    engine.chunk_steps))
    got = probe_logits(engine, [prompt])[0]
    want = probe_logits(ref, [prompt])[0]
    return rel_error(got, want, cfg.vocab_size)


def decode_program_has_kernels(engine) -> bool:
    """Whether the compiled decode-chunk program calls the Pallas TPU
    kernels (``tpu_custom_call``) rather than jnp or the interpreter."""
    compiled = engine._decode_chunk.lower(engine.params,
                                          engine.state).compile()
    return "tpu_custom_call" in compiled.as_text()


def peak_bytes(dev) -> tuple:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def one_chip_phase(cfg, system, *, prompt_lens=PROMPT_LENS,
                   n_requests=N_REQUESTS, new_tokens=NEW_TOKENS, qps=QPS,
                   kw=None, clog=None) -> float:
    """Build, warm up, serve, probe.  Returns the kernel-vs-jnp logit
    error (checked against ``PALLAS_VS_JNP_RTOL``)."""
    clog = clog or CompileLog()
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(model, params, **(kw or engine_kw()))
    print(f"[1] {cfg.name}: {param_count(model.param_defs()) / 1e9:.3f} B "
          f"params ({cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}); use_pallas={cfg.use_pallas}; "
          f"{engine.n_slots} slots, max_len {engine.max_len}, "
          f"page {engine.page_size}")

    prompts = make_prompts(cfg.vocab_size, n_requests, prompt_lens, seed=0)
    s0, n0, h0 = clog.snapshot()
    wall = warm_up(engine, prompts[:len(prompt_lens)], new_tokens)
    s1, n1, h1 = clog.snapshot()
    print(f"[2] warm-up of prompt lengths {list(prompt_lens)}: {wall:.2f} s "
          f"wall, {s1 - s0:.2f} s compiling {n1 - n0} programs "
          f"({h1 - h0} persistent-cache hits)")

    result = serve_measured(ContinuousBatchingSUT, engine, cfg, system,
                            prompts, new_tokens, qps, f"{cfg.name}-smoke")
    s2, n2, _ = clog.snapshot()
    report_serving(result, system, "[3]")
    print(f"[3] compilations inside the window: {n2 - n1} "
          f"({s2 - s1:.2f} s)")

    # compiled kernels are what a TPU config runs; the interpreter and
    # the jnp path lower to no TPU kernel
    expect = cfg.use_pallas and not cfg.pallas_interpret
    has = decode_program_has_kernels(engine)
    print(f"[4] decode-chunk program holds tpu_custom_call: {has}")
    check(has == expect, "compiled decode chunk lacks the Pallas TPU "
          "kernels" if expect else "TPU kernel in a program off the chip")

    probe_logits(engine, prompts[:len(prompt_lens)])
    err = kernel_parity(engine, prompts[len(prompt_lens) - 1])
    print(f"[5] logits finite after every prompt length; kernel vs jnp "
          f"relative L2 error {err:.3e} (bound {PALLAS_VS_JNP_RTOL:.0e})")
    check(err <= PALLAS_VS_JNP_RTOL,
          f"kernel vs jnp logit error {err:.3e} > {PALLAS_VS_JNP_RTOL:.0e}")
    return err


def four_chip_phase(cfg, system, mesh, *, ref_layers=TP4_REFERENCE_LAYERS,
                    prompt_lens=TP4_PROMPT_LENS, n_requests=TP4_REQUESTS,
                    new_tokens=TP4_NEW_TOKENS, qps=TP4_QPS, kw=None,
                    clog=None) -> float:
    """Serve ``cfg`` at TP=len(mesh) with its parameters created in
    their shards, then compare one decode step's logits at depth
    ``ref_layers`` with the unsharded engine on the first device.
    Returns the logit error (checked against ``TP4_VS_ONE_CHIP_RTOL``)."""
    clog = clog or CompileLog()
    kw = kw or engine_kw()
    devs = list(mesh.devices.flat)
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0),
                         tp_param_shardings(model, mesh))
    engine = ShardedContinuousBatchingEngine(model, params, mesh=mesh, **kw)
    print(f"[1] {cfg.name}: {param_count(model.param_defs()) / 1e9:.3f} B "
          f"params ({cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}) created in their shards over TP={engine.tp}; "
          f"use_pallas={cfg.use_pallas}")

    prompts = make_prompts(cfg.vocab_size, n_requests, prompt_lens, seed=0)
    s0, n0, h0 = clog.snapshot()
    wall = warm_up(engine, prompts[:len(prompt_lens)], new_tokens)
    s1, n1, h1 = clog.snapshot()
    print(f"[2] warm-up: {wall:.2f} s wall, {s1 - s0:.2f} s compiling "
          f"{n1 - n0} programs ({h1 - h0} persistent-cache hits)")
    result = serve_measured(ShardedSUT, engine, cfg, system, prompts,
                            new_tokens, qps, f"{cfg.name}-tp{engine.tp}")
    s2, n2, _ = clog.snapshot()
    report_serving(result, system, "[3]")
    print(f"[3] compilations inside the window: {n2 - n1} "
          f"({s2 - s1:.2f} s)")
    for d in devs:
        peak, limit = peak_bytes(d)
        print(f"[3] device {d.id} peak_bytes_in_use {peak} "
              f"(bytes_limit {limit})")
        if peak is not None and limit is not None:
            check(peak <= limit, f"device {d.id} peak {peak} > {limit}")
    del engine, params, result
    gc.collect()

    cut = dataclasses.replace(cfg, n_layers=ref_layers)
    model = build_model(cut)
    params = init_params(model.param_defs(), jax.random.PRNGKey(1),
                         tp_param_shardings(model, mesh))
    sharded = ShardedContinuousBatchingEngine(model, params, mesh=mesh,
                                              **kw)
    prompt = prompts[0]
    got = probe_logits(sharded, [prompt])[0]
    one = ContinuousBatchingEngine(
        model, jax.device_put(sharded.params, devs[0]), **kw)
    want = probe_logits(one, [prompt])[0]
    err = rel_error(got, want, cfg.vocab_size)
    print(f"[4] {ref_layers}-layer cut: TP={sharded.tp} vs one device "
          f"relative L2 logit error {err:.3e} "
          f"(bound {TP4_VS_ONE_CHIP_RTOL:.0e})")
    check(err <= TP4_VS_ONE_CHIP_RTOL,
          f"TP vs one-device logit error {err:.3e} > "
          f"{TP4_VS_ONE_CHIP_RTOL:.0e}")
    return err


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="run only the four-chip phase: yi-9b at TP=4 "
                         "and its one-chip reference")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.tp4 else 1)
    print(f"[0] {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{use_compile_cache()}")
    system = system_for_device(devs[0])
    with CompileLog() as clog:
        if args.tp4:
            four_chip_phase(for_platform(get_config(TP4_ARCH)), system,
                            make_tp_mesh(4), clog=clog)
        else:
            one_chip_phase(for_platform(get_config(ARCH)), system,
                           clog=clog)
            peak, limit = peak_bytes(devs[0])
            print(f"[6] peak_bytes_in_use {peak} (bytes_limit {limit})")
        secs, progs, hits = clog.snapshot()
        print(f"[7] total: {secs:.2f} s compiling {progs} programs "
              f"({hits} persistent-cache hits)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
