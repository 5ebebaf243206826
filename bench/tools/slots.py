#!/usr/bin/env python3
"""Compile a configuration's decode-chunk and longest-prefill programs
for a described TPU v5e (no chip needed) at several slot counts, and
print what ``memory_analysis`` says each needs on one chip.

    JAX_PLATFORMS=cpu python3 bench/tools/slots.py qwen3-1.7b 16 32 \
        --prompt 1984

A slot count is kept where both programs, with the page pool at
``max_len`` tokens per slot, leave at least 2 GiB of the chip free.
With ``--tp 4`` the programs are compiled for four described chips at
tensor parallelism and the figures are per chip.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GiB = 2 ** 30


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("slots", type=int, nargs="+")
    ap.add_argument("--prompt", type=int, required=True)
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import served
    from repro.models import build_model
    from repro.models.param import abstract_params
    from repro.serving import ContinuousBatchingEngine
    from repro.serving.sharded import ShardedContinuousBatchingEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{args.config}.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = topo.devices[:args.tp]
    mesh = Mesh(devs, ("model",))
    rep = NamedSharding(mesh, P())
    mc = dataclasses.replace(served.program_config(cfg), use_pallas=True)
    model = build_model(mc)

    def sds(tree, shardings=None):
        if shardings is None:
            shardings = jax.tree.map(lambda _: rep, tree)
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    for n in args.slots:
        e = cfg["engine"]
        kw = dict(n_slots=n, max_len=e["max_len"],
                  kv_page_size=e["kv_page_size"],
                  chunk_steps=e["chunk_steps"])
        params = abstract_params(model.param_defs())
        if args.tp == 1:
            class Eng(ContinuousBatchingEngine):
                def _zeros(self, specs):
                    return specs
            eng = Eng(model, params, **kw)
            pspec = None
            state = sds(eng.state)
        else:
            class Eng(ShardedContinuousBatchingEngine):
                def _zeros(self, specs):
                    return specs

                def reset(self):
                    ContinuousBatchingEngine.reset(self)

            jax.device_put = lambda x, *a, **k: x   # shapes only
            eng = Eng(model, params, mesh=mesh, **kw)
            pspec = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                 eng._param_specs)
            sspec = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                 eng._state_specs)
            state = sds(eng.state, sspec)
        params = sds(params, pspec)
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        toks = jax.ShapeDtypeStruct((1, args.prompt), jnp.int32,
                                    sharding=rep)
        row = jax.ShapeDtypeStruct((eng.pages_per_slot,), jnp.int32,
                                   sharding=rep)
        progs = {
            "decode_chunk": eng._decode_chunk.lower(params, state),
            f"prefill_{args.prompt}": eng._prefill_slot.lower(
                params, None, state, toks, scalar, scalar, row),
        }
        for name, low in progs.items():
            ma = low.compile().memory_analysis()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            print(f"{args.config} slots={n} tp={args.tp} {name}: "
                  f"arguments {ma.argument_size_in_bytes / GiB:.3f} GiB, "
                  f"outputs {ma.output_size_in_bytes / GiB:.3f}, aliased "
                  f"{ma.alias_size_in_bytes / GiB:.3f}, temporaries "
                  f"{ma.temp_size_in_bytes / GiB:.3f}; total "
                  f"{total / GiB:.3f} GiB of 15.75", flush=True)


if __name__ == "__main__":
    main()
