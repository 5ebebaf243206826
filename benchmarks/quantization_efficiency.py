"""Fig. 8: quantization at a fixed accuracy target — a REAL accuracy
measurement on the tiny model (int8 vs fp32 logits agreement on CPU, the
99%/99.9% target premise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row


def run() -> list[dict]:
    return [tiny_accuracy_measurement()]


def tiny_accuracy_measurement() -> dict:
    """REAL CPU measurement: int8-quantized tiny model vs fp32."""
    from repro.configs import get_config
    from repro.kernels.int8_matmul import int8_matmul, quantize_int8
    from repro.models.param import init_params
    from repro.models.tiny import IN_F, IN_T, TinyModel

    cfg = get_config("tiny-kws")
    model = TinyModel(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, IN_T, IN_F))
    ref = model(params, x)
    # int8 path on the dominant pointwise convs (pw matmuls)
    h = x @ params["stem"]
    for i in range(cfg.n_layers):
        w = params[f"dw{i}"]
        hp = jnp.pad(h, ((0, 0), (1, 1), (0, 0)))
        conv = sum(hp[:, j:j + h.shape[1]] * w[j] for j in range(3))
        cq, sx = quantize_int8(conv.reshape(-1, conv.shape[-1]), axis=1)
        wq, sw = quantize_int8(params[f"pw{i}"], axis=0)
        out = int8_matmul(cq, wq, sx, sw, out_dtype=jnp.float32,
                          interpret=True)
        h = jax.nn.relu(out.reshape(conv.shape) + params[f"b{i}"])
    q_logits = h.mean(axis=1) @ params["head"]
    agree = float(jnp.mean(jnp.argmax(q_logits, -1) == jnp.argmax(ref, -1)))
    rel = float(jnp.linalg.norm(q_logits - ref) / jnp.linalg.norm(ref))
    return {"arch": "tiny-kws(real)", "int8_argmax_agreement": agree,
            "int8_rel_err": rel}


def csv() -> list[str]:
    return [csv_row(
        "fig8_quant[tiny_accuracy]", 0.0,
        f"agree={r['int8_argmax_agreement']:.4f};"
        f"rel={r['int8_rel_err']:.4f}") for r in run()]


if __name__ == "__main__":
    for r in run():
        print(r)
