"""The plain reference against the program's engine on the CPU, at a
small size: prefill into a paged slot and paged decode (the Pallas
kernel in the interpreter) must give the reference's logits."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import check, served, spec  # noqa: E402
from bench import weights as W  # noqa: E402


def small(dtype, qk_norm=True):
    cfg = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=300, torch_dtype=dtype, qk_norm=qk_norm)
    cfg["engine"].update(n_slots=3, max_len=64, kv_page_size=16)
    return cfg


REF = spec.load_module(BENCH / "reference" / "dense_gqa.py", "dense_gqa")


def ref_logits(cfg, seed, toks):
    """Reference logits (S, vocab) of one sequence."""
    key = W.base_key(seed)
    dt = jnp.dtype(cfg["torch_dtype"])
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    ref = REF.Reference(cfg)
    x = f32(W.global_leaf(key, "embed", cfg, dt))[jnp.asarray(toks)]
    for layer in range(cfg["num_hidden_layers"]):
        w = {n: f32(W.layer_leaf(key, n, layer, cfg, dt))
             for n in W.layer_names(cfg)}
        x = ref.block(x, w, "f32")
    return np.asarray(ref.head(x, f32(W.global_leaf(key, "final_norm", cfg,
                                                    dt)),
                               f32(W.global_leaf(key, "lm_head", cfg, dt)),
                               "f32"))


@pytest.mark.parametrize("qk_norm", [True, False])
def test_engine_prefill_and_paged_decode_match_the_reference(qk_norm):
    cfg = small("float32", qk_norm)
    seed = 2 ** 35 + 3
    _, engine = served.build_engine(cfg, seed, jax.devices()[:1],
                                    pallas_interpret=True)
    assert engine.paged and engine.model.cfg.use_pallas
    rng = np.random.default_rng(0)
    for s in (5, 23, 40):
        prompt = rng.integers(0, cfg["vocab_size"], s, dtype=np.int32)
        # the first decode step's logits, after a prefill into a slot
        got = np.asarray(engine.first_step_logits(prompt))[
            :cfg["vocab_size"]]
        tok0 = int(ref_logits(cfg, seed, prompt)[-1].argmax())
        want = ref_logits(cfg, seed, np.append(prompt, tok0))[-1]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_served_tokens_have_no_gap_in_float32():
    """Served through ``serve`` (several slots, pages written and read
    across decode chunks), every token is the reference's best."""
    from repro.serving import Request

    cfg = small("float32")
    seed = 5
    _, engine = served.build_engine(cfg, seed, jax.devices()[:1],
                                    pallas_interpret=True)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 300, s, dtype=np.int32),
                    max_new_tokens=n)
            for i, (s, n) in enumerate([(7, 20), (30, 9), (16, 33),
                                        (2, 12)])]
    done = engine.serve(reqs, honor_arrivals=False)
    seqs = [(np.asarray(r.prompt), r.output) for r in done]
    gaps = check.served_gaps(REF, cfg, seed, seqs, 64)["served"]
    assert max(gaps) < 1e-4, gaps
