"""Whether the timed path served the right tokens.

Once the window has closed and the program's state is freed, a sample
of the requests it finished, drawn from the seed and always holding
the request with the most served tokens, runs through the plain
reference: each prompt followed by its served tokens, the whole
sequence at once.  For every served token the reference gives the gap
by which that token's logit lies below the best logit at its position.
The number compared is the widest gap over the sample.  Greedy
decoding serves the best token, so a sound program reads rounding
noise here, and a token altered anywhere on the path reads the spread
of the logits.

The reference is rebuilt from the seed layer by layer (the weights are
the benchmark's own, ``bench.weights``), one sequence at a time, padded
to one length so that one program serves every run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W


def sample(done: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from ``seed``: the one with the most
    served tokens first, then others at random."""
    if not done:
        return []
    order = sorted(done, key=lambda r: (-len(r.output), r.rid))
    rest = order[1:]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def _layer_weights(cfg: dict):
    dt = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def get(key, layer):
        return {n: W.layer_leaf(key, n, layer, cfg, dt).astype(jnp.float32)
                for n in W.layer_names(cfg)}

    return get


def _global(cfg: dict, key, name: str):
    dt = jnp.dtype(cfg["torch_dtype"])
    return jax.jit(lambda k: W.global_leaf(k, name, cfg, dt).astype(
        jnp.float32))(key)


@jax.jit
def _gaps(ref_logits, other_logits, served):
    """Per position: the gap below the reference's best of the served
    token, and of the token ``other_logits`` puts first."""
    best = ref_logits.max(-1)
    at = jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
    first = jnp.argmax(other_logits, -1)
    at_other = jnp.take_along_axis(ref_logits, first[:, None], -1)[:, 0]
    return best - at, best - at_other


def served_gaps(ref_mod, cfg: dict, seed: int, seqs: list, pad_to: int,
                control: bool = False) -> dict:
    """Gaps of the served tokens under the reference.

    ``seqs``: [(prompt ids, served ids)].  Returns ``{"served": [per
    request max gap]}`` and, with ``control``, ``{"control": [...]}``:
    the gap of the token the fp8 control puts first at each of the same
    positions.
    """
    ref = ref_mod.Reference(cfg)
    key = W.base_key(seed)
    embed = _global(cfg, key, "embed")
    modes = ("f32", "fp8") if control else ("f32",)
    xs = {}
    for m in modes:
        xs[m] = []
        for prompt, served in seqs:
            toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            toks = np.pad(toks, (0, pad_to - len(toks)))
            xs[m].append(jnp.take(embed, jnp.asarray(toks), axis=0))
    del embed
    get = _layer_weights(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        w = get(key, layer)
        for m in modes:
            xs[m] = [ref.block(x, w, m) for x in xs[m]]
        del w
    norm = _global(cfg, key, "final_norm")
    head = _global(cfg, key, "lm_head")
    out = {"served": [], "control": []}
    for i, (prompt, served) in enumerate(seqs):
        pos = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
        # one program for every request: pad the positions to max_len
        idx = jnp.asarray(np.pad(pos, (0, pad_to - len(pos)), mode="edge"))
        tgt = jnp.asarray(np.pad(np.asarray(served, np.int32),
                                 (0, pad_to - len(pos)), mode="edge"))
        logits = {m: ref.head(xs[m][i][idx], norm, head, m) for m in modes}
        g, gc = _gaps(logits["f32"], logits[modes[-1]], tgt)
        n = len(pos)
        out["served"].append(float(np.asarray(g)[:n].max()))
        if control:
            out["control"].append(float(np.asarray(gc)[:n].max()))
    return out
