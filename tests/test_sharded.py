"""Tensor-parallel sharded serving: parity, replicas, energy accounting.

The expensive cases run in a subprocess with forced virtual host
devices (same pattern as test_multidevice); the cheap ones (tp=1
degenerate mesh, replica energy attribution, sysdesc scaling) run
in-process on the single real device.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_py(code: str, devices: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def _mixed_requests(cfg, n=6, prompt_len=12):
    from repro.serving import Request

    key = jax.random.PRNGKey(7)
    return [Request(rid=i, prompt=np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)),
        max_new_tokens=[5, 9, 3, 7][i % 4], arrival_s=0.0)
        for i in range(n)]


def test_tp4_token_identical_to_tp1():
    """TP=4 decode (with KV-head replication: reduced cfg has kvh=2)
    emits exactly the tokens the unsharded engine emits — ragged slots,
    mid-flight refills and all."""
    out = run_py("""
        import jax, numpy as np
        from repro.configs import get_config, reduce_config
        from repro.models import build_model
        from repro.models.param import init_params
        from repro.serving import (ContinuousBatchingEngine, Request,
                                   ShardedContinuousBatchingEngine)

        cfg = reduce_config(get_config("qwen3-1.7b"))
        model = build_model(cfg)
        params = init_params(model.param_defs(), jax.random.PRNGKey(0))

        def reqs():
            key = jax.random.PRNGKey(7)
            return [Request(rid=i, prompt=np.asarray(jax.random.randint(
                jax.random.fold_in(key, i), (12,), 0, cfg.vocab_size)),
                max_new_tokens=[5, 9, 3, 7][i % 4], arrival_s=0.0)
                for i in range(6)]

        base = ContinuousBatchingEngine(model, params, max_len=48,
                                        n_slots=3, chunk_steps=4)
        ref = sorted(base.serve(reqs(), honor_arrivals=False),
                     key=lambda r: r.rid)
        tp4 = ShardedContinuousBatchingEngine(model, params, tp=4,
                                              max_len=48, n_slots=3,
                                              chunk_steps=4)
        got = sorted(tp4.serve(reqs(), honor_arrivals=False),
                     key=lambda r: r.rid)
        assert len(ref) == len(got) == 6
        for a, b in zip(ref, got):
            assert a.output == b.output, (a.rid, a.output, b.output)
        assert tp4.tp == 4 and len(jax.devices()) == 4
        print("TP4-PARITY-OK")
    """)
    assert "TP4-PARITY-OK" in out


def test_decode_kernel_shard_map_parity():
    """The Pallas decode kernel (interpret mode) under shard_map with a
    KV-head-partitioned cache matches the full-cache call: per-shard
    block specs see B*KVH_local rows and a ragged pos vector."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.kernels.decode_attention.ops import decode_attention
        from repro.launch.mesh import make_tp_mesh

        b, h, kvh, d, s = 2, 8, 4, 32, 256
        pos = jnp.asarray([3, 200], jnp.int32)          # ragged depths
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
        kc = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
        vc = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)

        full = decode_attention(q, kc, vc, pos, interpret=True)

        mesh = make_tp_mesh(4)
        f = jax.shard_map(
            partial(decode_attention, interpret=True),
            mesh=mesh,
            in_specs=(P(None, None, "model", None),
                      P(None, None, "model", None),
                      P(None, None, "model", None), P()),
            out_specs=P(None, None, "model", None), check_vma=False)
        sharded = jax.jit(f)(q, kc, vc, pos)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)
        print("KERNEL-SHARD-OK")
    """)
    assert "KERNEL-SHARD-OK" in out


def test_tp1_sharded_engine_degenerates_to_base():
    """A 1-device mesh is the identity layout: the sharded engine and
    the base engine emit the same tokens on the real single device."""
    from repro.configs import get_config, reduce_config
    from repro.models import build_model
    from repro.models.param import init_params
    from repro.serving import (ContinuousBatchingEngine,
                               ShardedContinuousBatchingEngine)

    cfg = reduce_config(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    base = ContinuousBatchingEngine(model, params, max_len=48, n_slots=2,
                                    chunk_steps=4)
    ref = sorted(base.serve(_mixed_requests(cfg, n=4),
                            honor_arrivals=False), key=lambda r: r.rid)
    tp1 = ShardedContinuousBatchingEngine(model, params, tp=1,
                                          max_len=48, n_slots=2,
                                          chunk_steps=4)
    got = sorted(tp1.serve(_mixed_requests(cfg, n=4),
                           honor_arrivals=False), key=lambda r: r.rid)
    for a, b in zip(ref, got):
        assert a.output == b.output, (a.rid, a.output, b.output)


def test_params_and_kv_pool_created_in_their_shards():
    """init_params(..., tp_param_shardings) creates every leaf straight
    into its NamedSharding with the values it has replicated, the
    engine keeps those arrays as they are, and its page
    pool is created partitioned by KV head."""
    out = run_py("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduce_config
        from repro.launch.mesh import make_tp_mesh
        from repro.models import build_model
        from repro.models.param import init_params
        from repro.serving import (ShardedContinuousBatchingEngine,
                                   tp_param_shardings)

        import dataclasses
        cfg = dataclasses.replace(reduce_config(get_config("yi-9b")),
                                  n_kv_heads=4)
        model = build_model(cfg)
        mesh = make_tp_mesh(4)
        key = jax.random.PRNGKey(3)
        sh = tp_param_shardings(model, mesh)
        params = init_params(model.param_defs(), key, sh)
        # the same initializer with every leaf replicated
        plain = init_params(model.param_defs(), key, jax.tree.map(
            lambda _: NamedSharding(mesh, P()), sh))
        for a, b, s in zip(jax.tree.leaves(params), jax.tree.leaves(plain),
                           jax.tree.leaves(sh)):
            assert a.sharding == s, (a.sharding, s)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        wq = params["blocks"]["attn"]["wq"]
        assert wq.sharding.spec == P(None, None, "model"), wq.sharding
        eng = ShardedContinuousBatchingEngine(model, params, mesh=mesh,
                                              max_len=64, n_slots=2,
                                              kv_page_size=16)
        assert eng.params["blocks"]["attn"]["wq"] is wq
        pool = eng.state["cache"]["layers"]["blocks"]["k"]
        assert pool.sharding == NamedSharding(
            mesh, P(None, "model", None, None, None)), pool.sharding
        assert len(pool.addressable_shards) == 4
        assert pool.addressable_shards[0].data.shape[1] == 1
        print("PLACED-OK")
    """)
    assert "PLACED-OK" in out


def test_replicate_kv_heads_exact():
    """KV-head replication is an identity transform: the expanded model
    (kvh -> tp heads) decodes the same tokens as the original."""
    from repro.configs import get_config, reduce_config
    from repro.models import build_model
    from repro.models.param import init_params
    from repro.serving import ContinuousBatchingEngine
    from repro.serving.sharded import replicate_kv_heads

    cfg = reduce_config(get_config("qwen3-1.7b"))
    assert cfg.n_kv_heads == 2
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    model4, params4 = replicate_kv_heads(model, params, tp=4)
    assert model4.cfg.n_kv_heads == 4
    assert model4.cfg.head_dim == cfg.head_dim

    ref = ContinuousBatchingEngine(model, params, max_len=48, n_slots=2,
                                   chunk_steps=4)
    exp = ContinuousBatchingEngine(model4, params4, max_len=48,
                                   n_slots=2, chunk_steps=4)
    a = sorted(ref.serve(_mixed_requests(cfg, n=4),
                         honor_arrivals=False), key=lambda r: r.rid)
    b = sorted(exp.serve(_mixed_requests(cfg, n=4),
                         honor_arrivals=False), key=lambda r: r.rid)
    for x, y in zip(a, b):
        assert x.output == y.output, (x.rid, x.output, y.output)


def _make_replica_sut(cfg, model, params, name):
    from repro.harness import ContinuousBatchingSUT
    from repro.serving import ContinuousBatchingEngine, Request

    engine = ContinuousBatchingEngine(model, params, max_len=48,
                                      n_slots=2, chunk_steps=4)
    key = jax.random.PRNGKey(3)

    def make_request(i, s, a):
        from repro.core.loadgen import qid_of

        rid = qid_of(s, i)
        return Request(rid=rid, prompt=np.asarray(jax.random.randint(
            jax.random.fold_in(key, rid), (8,), 0, cfg.vocab_size)),
            max_new_tokens=4, arrival_s=float(a))

    return ContinuousBatchingSUT(engine, cfg, name=name,
                                 make_request=make_request)


def test_replica_energy_sums_to_fleet_total():
    """ReplicatedSUT: per-replica energy attribution sums to the fleet
    trace's integral, and the measured fleet energy agrees within the
    analyzer's error budget."""
    from repro.configs import get_config, reduce_config
    from repro.core.analyzer import AnalyzerSpec, VirtualAnalyzer
    from repro.core.director import Director
    from repro.harness import PowerRun, ReplicatedSUT, Server
    from repro.models import build_model
    from repro.models.param import init_params

    cfg = reduce_config(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    reps = [_make_replica_sut(cfg, model, params, f"rep{i}")
            for i in range(2)]
    fleet = ReplicatedSUT(reps, name="fleet")
    scenario = Server(target_qps=100.0, latency_slo_s=30.0,
                      min_duration_s=0.0, min_queries=8, mode="queue")
    director = Director(analyzer=VirtualAnalyzer(
        AnalyzerSpec(sample_hz=1000.0), seed=0), seed=0)
    r = PowerRun(fleet, scenario, seed=0, director=director).run()

    # every request completed exactly once, fleet-unique rids
    rids = [req.rid for req in fleet.completed]
    assert len(rids) == len(set(rids)) == 8
    # both replicas actually served
    assert all(rep.completed for rep in reps)

    times_s, watts = r.power_samples()
    per_replica = fleet.replica_energy_j(r.outcome, times_s)
    assert len(per_replica) == 2 and all(e > 0 for e in per_replica)
    from repro.core.summarizer import _trapz
    fleet_trapz = float(_trapz(watts, times_s))
    # attribution is exact up to analyzer noise (0.1% gain + offset)
    assert abs(sum(per_replica) - fleet_trapz) / fleet_trapz < 0.02
    assert abs(sum(per_replica) - r.summary.energy_j) \
        / r.summary.energy_j < 0.05
    # per-request energy attribution covers the fleet
    assert r.per_request_energy_j is not None
    assert set(r.per_request_energy_j) == set(rids)


def test_idle_replica_billed_at_idle_floor():
    """ReplicatedSUT idle-energy guard: a replica whose round-robin
    share is empty still draws its idle floor for the whole window —
    billed into the fleet total, not silently zero (the fleet-J/token
    denominator must include provisioned-but-idle capacity)."""
    from repro.configs import get_config, reduce_config
    from repro.core.analyzer import AnalyzerSpec, VirtualAnalyzer
    from repro.core.director import Director
    from repro.core.summarizer import _trapz
    from repro.harness import PowerRun, ReplicatedSUT, Server
    from repro.models import build_model
    from repro.models.param import init_params

    cfg = reduce_config(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    reps = [_make_replica_sut(cfg, model, params, f"rep{i}")
            for i in range(3)]
    fleet = ReplicatedSUT(reps, name="fleet")
    # 2 queries round-robin over 3 replicas: replica 2's share is empty
    scenario = Server(target_qps=100.0, latency_slo_s=30.0,
                      min_duration_s=0.0, min_queries=2, mode="queue")
    director = Director(analyzer=VirtualAnalyzer(
        AnalyzerSpec(sample_hz=1000.0), seed=0), seed=0)
    r = PowerRun(fleet, scenario, seed=0, director=director).run()

    assert not reps[2].completed and len(fleet.completed) == 2

    times_s, watts = r.power_samples()
    per_replica = fleet.replica_energy_j(r.outcome, times_s)
    assert len(per_replica) == 3
    # the idle replica is billed exactly its idle floor x window
    window_s = float(times_s[-1] - times_s[0])
    idle_w = float(reps[2].meter.system_watts(None))
    assert per_replica[2] > 0.0
    assert abs(per_replica[2] - idle_w * window_s) \
        / (idle_w * window_s) < 1e-6
    # serving replicas drew strictly more than the idle floor
    assert per_replica[0] > per_replica[2]
    assert per_replica[1] > per_replica[2]
    # and attribution still sums to the measured fleet trace
    fleet_trapz = float(_trapz(watts, times_s))
    assert abs(sum(per_replica) - fleet_trapz) / fleet_trapz < 0.02


def test_scaled_sysdesc_envelopes():
    """ShardedSUT / ReplicatedSUT declare scale-matched envelopes: tp
    chips on the meter, replica sums on the fleet description."""
    import types

    from repro.harness import ReplicatedSUT, ShardedSUT

    cfg = types.SimpleNamespace(param_count=lambda: 1_000_000)
    engine = types.SimpleNamespace(tp=4, n_slots=4)
    sut = ShardedSUT(engine, cfg, make_request=lambda i, s, a: None)
    desc = sut.system_description()
    assert desc.scale == "datacenter" and desc.n_chips == 4
    assert desc.telemetry_accuracy is not None
    assert desc.max_system_watts > desc.idle_system_watts > 0

    one = types.SimpleNamespace(tp=1, n_slots=4)
    single = ShardedSUT(one, cfg, make_request=lambda i, s, a: None)
    sdesc = single.system_description()
    assert sdesc.scale == "edge"

    fleet = ReplicatedSUT([single, single, single])
    fdesc = fleet.system_description()
    assert fdesc.n_chips == 3 * sdesc.n_chips
    assert np.isclose(fdesc.idle_system_watts,
                      3 * sdesc.idle_system_watts)
    assert np.isclose(fdesc.max_system_watts,
                      3 * sdesc.max_system_watts)
