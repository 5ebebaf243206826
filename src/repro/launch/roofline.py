"""Roofline extraction from compiled XLA artifacts.

Sources:
- ``compiled.cost_analysis()``: per-device HLO FLOPs + bytes accessed.
- ``compiled.as_text()``: post-SPMD per-device HLO; collective bytes are
  the summed operand sizes of every all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute instruction.
- ``compiled.memory_analysis()``: per-device argument/temp/output bytes.

Terms (seconds, per the assignment's formulas, TPU v5e constants):
  compute    = HLO_FLOPs / peak_FLOP/s        (per-device flops)
  memory     = HLO_bytes / HBM_bw
  collective = collective_bytes / link_bw
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from repro.hw import TPU_V5E, ChipSpec

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"= .*?\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:                       # iota format [n_groups, group_size]<=[N]
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, n_devices: int = 2) -> dict[str, float]:
    """Per-device wire bytes per collective kind, from post-SPMD HLO.

    The CPU HLO dump prints result types only, so bytes are derived from
    result sizes + group size g with the standard ring/all-to-all cost
    model (per participating device):
      all-reduce:         2 * size * (g-1)/g       (reduce-scatter+AG ring)
      all-gather:         result * (g-1)/g         (result = gathered size)
      reduce-scatter:     result * (g-1)            (input = result * g)
      all-to-all:         size * (g-1)/g
      collective-permute: result
    """
    out: dict[str, float] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        mm = _COLL_RE.search(line)
        if not mm:
            continue
        head = line[: mm.end()]
        if "-done" in head.rsplit("=", 1)[-1]:
            continue
        kind = mm.group(1)
        # result types sit between '=' and the opcode
        eq = line.index("= ")
        result_sec = line[eq:mm.end()]
        size = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(result_sec))
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        frac = (g - 1) / g
        if kind == "all-reduce":
            b = 2.0 * size * frac
        elif kind == "all-gather":
            b = size * frac
        elif kind == "reduce-scatter":
            b = size * (g - 1)
        elif kind == "all-to-all":
            b = size * frac
        else:                   # collective-permute
            b = float(size)
        out[kind] = out.get(kind, 0.0) + b
        count[kind] = count.get(kind, 0) + 1
    out["_counts"] = count
    return out


@dataclasses.dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    # per-device quantities
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_breakdown: dict
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    # model-level sanity
    model_flops: float            # global useful FLOPs (6ND / 2ND)
    model_flops_ratio: float      # model_flops / (flops * n_devices)
    # memory analysis (per device, bytes)
    arg_bytes: int = 0
    temp_bytes: int = 0
    out_bytes: int = 0
    alias_bytes: int = 0
    fits_hbm: bool = True
    step_s: float = 0.0
    # analytic HBM floor (weights/cache must stream at least once):
    # HLO 'bytes accessed' is pre-fusion and thus an upper bound; the
    # floor bounds the truth from below.
    min_hbm_bytes: float = 0.0
    memory_floor_s: float = 0.0
    notes: str = ""


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (forward-only); N = active
    matmul params (MoE counts top-k + shared only; the input-embedding
    table is a gather, not a matmul, so it is excluded — the lm_head
    remains counted).  D = tokens processed."""
    n = cfg.active_param_count() if cfg.moe is not None else cfg.param_count()
    n -= cfg.vocab_size * cfg.d_model          # input embedding gather
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token each


def analyze(cell, compiled, *, chip: ChipSpec = TPU_V5E,
            mesh_name: str = "") -> CellReport:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    n_dev = int(np.prod(list(cell.mesh.shape.values()))) if cell.mesh else 1
    coll = collective_bytes(txt, n_dev)
    counts = coll.pop("_counts", {})
    cbytes = float(sum(coll.values()))

    t_c = flops / chip.peak_flops_bf16
    t_m = hbm / chip.hbm_bandwidth
    t_x = cbytes / chip.ici_bandwidth
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    mf = model_flops_for(cell.cfg, cell.shape)

    mem = compiled.memory_analysis()
    arg = int(getattr(mem, "argument_size_in_bytes", 0))
    tmp = int(getattr(mem, "temp_size_in_bytes", 0))
    outb = int(getattr(mem, "output_size_in_bytes", 0))
    alias = int(getattr(mem, "alias_size_in_bytes", 0))
    live = arg + tmp
    min_hbm = analytic_min_bytes(cell.cfg, cell.shape, n_dev)

    return CellReport(
        arch=cell.cfg.name, shape=cell.shape.name, mesh=mesh_name,
        n_devices=n_dev, flops=flops, hbm_bytes=hbm, coll_bytes=cbytes,
        coll_breakdown={**{k: float(v) for k, v in coll.items()},
                        "counts": counts},
        compute_s=t_c, memory_s=t_m, collective_s=t_x,
        bottleneck=max(terms, key=terms.get),
        model_flops=mf,
        model_flops_ratio=mf / max(flops * n_dev, 1.0),
        arg_bytes=arg, temp_bytes=tmp, out_bytes=outb, alias_bytes=alias,
        fits_hbm=live <= chip.hbm_capacity,
        step_s=max(t_c, t_m, t_x),
        min_hbm_bytes=min_hbm,
        memory_floor_s=min_hbm / chip.hbm_bandwidth,
    )


def analytic_min_bytes(cfg, shape, n_devices: int) -> float:
    """Per-device HBM traffic floor: parameters (and KV/state cache)
    must stream from HBM at least once per step; training adds grad and
    optimizer-state traffic; activations add one write+read per layer."""
    p_bytes = cfg.param_count() * 2 / n_devices            # bf16 shards
    tokens = shape.global_batch * shape.seq_len / n_devices
    act = tokens * cfg.d_model * 2 * cfg.n_layers * 2      # write+read
    if shape.kind == "train":
        # params fwd+bwd reads, grad write+read, opt m/v f32 rw, fp32 upd
        return 4 * p_bytes + 2 * p_bytes + 4 * 2 * 2 * p_bytes + act
    if shape.kind == "prefill":
        return p_bytes + act
    # decode: params + cache read (+ small write)
    cache = 0.0
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        per_tok = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        cache = cfg.n_layers * shape.seq_len * per_tok * 2
    elif cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.hybrid.attn_period
        cache = n_attn * shape.seq_len * kvh * dh * 2 * 2
        d_in = cfg.mamba.expand * cfg.d_model
        cache += (cfg.n_layers - n_attn) * d_in * cfg.mamba.d_state * 4
    elif cfg.family == "rwkv":
        from repro.models.rwkv6 import padded_heads
        cache = cfg.n_layers * padded_heads(cfg) * dh * dh * 4
    elif cfg.family == "encdec":
        cache = cfg.n_layers * (shape.seq_len + cfg.encdec.enc_len) \
            * kvh * dh * 2 * 2
    else:
        cache = cfg.n_layers * shape.seq_len * kvh * dh * 2 * 2
    return p_bytes + cache * shape.global_batch / n_devices
