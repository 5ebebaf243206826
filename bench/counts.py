"""Work the algorithm needs, from the configuration's shapes.

These count what a dense GQA decoder must compute and read, never what
an implementation happens to do: no transposes, no padding, no pages
read past a sequence's end.  Keys are the published ``config.json``
names.
"""
from __future__ import annotations


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """K and V of one token over every layer."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * bytes_per_el)


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply each token: the blocks' projections and
    the output head (the embedding is a gather)."""
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    dh, f = cfg["head_dim"], cfg["intermediate_size"]
    per_layer = d * (h + 2 * kvh) * dh + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attn_flops(cfg: dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    cached tokens, every layer: 2 products of 2 FLOPs each."""
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def decode_token_flops(cfg: dict, context: int) -> int:
    """One decoded token that attends to ``context`` tokens."""
    return 2 * matmul_params(cfg) + attn_flops(cfg, context)


def paged_attn_call(cfg: dict, contexts, bytes_per_el: int = 2,
                    shards: int = 1) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's decode attention on one chip, for
    live slots at ``contexts``: each slot's K and V rows, its query and
    its output; heads split over ``shards`` chips."""
    h = cfg["num_attention_heads"] // shards
    kvh = max(1, cfg["num_key_value_heads"] // shards)
    dh = cfg["head_dim"]
    flops = sum(4 * h * dh * c for c in contexts)
    kv = sum(2 * kvh * dh * c for c in contexts) * bytes_per_el
    qo = 2 * len(contexts) * h * dh * bytes_per_el
    return flops, kv + qo


def decode_contexts(prompt_len: int, n_out: int) -> range:
    """Context length of each decode step of a request: the token
    produced from the prefill is fed back at position ``prompt_len`` and
    attends to ``prompt_len + 1`` rows, and so on; the last of the
    ``n_out`` tokens is never fed back."""
    return range(prompt_len + 1, prompt_len + n_out)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Causal prefill of one prompt (the output head only for its last
    position)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    body = 2 * (matmul_params(cfg) - d * v) * prompt_len
    causal_pairs = prompt_len * (prompt_len + 1) // 2
    return body + 2 * d * v + 4 * cfg["num_hidden_layers"] \
        * cfg["num_attention_heads"] * cfg["head_dim"] * causal_pairs
