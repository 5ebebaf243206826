"""chip_smoke.py on the CPU: its phases at reduced size, its refusals,
and the platform-keyed choices it relies on (kernel path, modeled
system, compile cache)."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import jax
import pytest

from repro.configs import for_platform, get_config, reduce_config
from repro.hw import (DATACENTER_V5E, EDGE_SYSTEM, SYSTEMS_BY_DEVICE_KIND,
                      system_for_device)
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


def _small(arch):
    """A reduced config whose kernels run in the Pallas interpreter."""
    return for_platform(dataclasses.replace(
        reduce_config(get_config(arch)), pallas_interpret=True))


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("system", [EDGE_SYSTEM, DATACENTER_V5E],
                         ids=["edge", "v5e"])
def test_one_chip_phase_at_reduced_size(system, capsys):
    cfg = _small("qwen3-1.7b")
    with cs.CompileLog() as clog:
        err = cs.one_chip_phase(
            cfg, system, prompt_lens=(16, 40), n_requests=6, new_tokens=5,
            qps=20.0, kw=cs.engine_kw(slots=3, max_len=64, page=16,
                                      chunk_steps=4), clog=clog)
        assert clog.programs > 0
    out = capsys.readouterr().out
    assert err <= cs.PALLAS_VS_JNP_RTOL
    assert "served 6 requests, 30 tokens" in out
    assert "compilations inside the window: 0" in out
    assert f"x {system.chip.name} system watts" in out


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    code = textwrap.dedent(f"""
        import dataclasses, importlib.util
        spec = importlib.util.spec_from_file_location("cs", {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import for_platform, get_config, reduce_config
        from repro.hw import DATACENTER_V5E
        from repro.launch.mesh import make_tp_mesh
        cfg = for_platform(dataclasses.replace(
            reduce_config(get_config("yi-9b")), n_kv_heads=4,
            pallas_interpret=True))
        with cs.CompileLog() as clog:
            err = cs.four_chip_phase(
                cfg, DATACENTER_V5E, make_tp_mesh(4), ref_layers=2,
                prompt_lens=(24,), n_requests=4, new_tokens=5, qps=20.0,
                kw=cs.engine_kw(slots=3, max_len=64, page=16,
                                chunk_steps=4), clog=clog)
        print("TP4-PHASE-OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "TP4-PHASE-OK" in r.stdout
    assert "compilations inside the window: 0" in r.stdout
    assert "2-layer cut: TP=4 vs one device" in r.stdout


def test_entry_point_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not _json_lines(r.stdout)


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, SCRIPT.name], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode != 0
    assert not _json_lines(r.stdout)


def test_require_tpu_exits_on_cpu():
    with pytest.raises(SystemExit) as e:
        cs.require_tpu()
    assert "no TPU" in str(e.value)


def test_kernel_path_follows_the_platform(monkeypatch):
    cfg = get_config("qwen3-1.7b")
    assert not for_platform(cfg).use_pallas            # CPU: jnp path
    interp = dataclasses.replace(cfg, pallas_interpret=True)
    assert for_platform(interp).use_pallas             # CPU: interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert for_platform(cfg).use_pallas                # TPU: kernels
    with pytest.raises(ValueError, match="interpreter"):
        for_platform(interp)


def test_modeled_system_keyed_by_device_kind():
    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert system_for_device(dev("tpu", "TPU v5 lite")) is DATACENTER_V5E
    assert SYSTEMS_BY_DEVICE_KIND["TPU v5 lite"].chip.peak_flops_bf16 \
        == 197e12
    assert system_for_device(dev("cpu", "cpu")) is EDGE_SYSTEM
    with pytest.raises(ValueError, match="TPU v6 lite"):
        system_for_device(dev("tpu", "TPU v6 lite"))
    assert system_for_device(jax.devices()[0]) is EDGE_SYSTEM


def test_compile_cache_placed_from_outside(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_result_line_is_the_contract_json(monkeypatch, capsys):
    """The last stdout line names the device as JAX reports it (the
    phases themselves are stubbed: they run above at reduced size)."""
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                id=0, memory_stats=lambda: None)
    monkeypatch.setattr(cs.jax, "devices", lambda: [tpu])
    monkeypatch.setattr(cs, "use_compile_cache", lambda: "(stub)")
    monkeypatch.setattr(cs, "one_chip_phase", lambda *a, **k: 0.0)
    cs.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
