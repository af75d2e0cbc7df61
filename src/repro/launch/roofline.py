"""Roofline terms from a compiled dry-run artifact.

Three terms per (arch x shape x mesh) cell, all in seconds (lower bound on
step time if that resource were the only one):

  compute    = HLO_FLOPs_per_device / PEAK_FLOPS_BF16
  memory     = HLO_bytes_per_device / HBM_BW
  collective = link_bytes_per_device / ICI_BW

``cost_analysis()`` on the SPMD-partitioned module reports *per-device*
flops / bytes (verified empirically: a (32,64)x(64,128) matmul over a
(2,4) mesh reports B/2 * F/4 flops).  Collective bytes are NOT in
cost_analysis; we parse the post-partitioning HLO and apply standard ring
cost models per op (bytes that cross links, per device):

  all-gather          result_bytes * (g-1)/g
  all-reduce          2 * result_bytes * (g-1)/g     (reduce-scatter + AG)
  reduce-scatter      result_bytes * (g-1)            (operand = result * g)
  all-to-all          result_bytes * (g-1)/g
  collective-permute  result_bytes

where ``g`` is the replica-group size parsed from the op.  Shapes in the
partitioned module are already per-shard, so the sums are per-device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

from .mesh import PEAK_FLOPS_BF16, HBM_BW, ICI_BW

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)          # [n_groups,group_size]<=...
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)             # {{0,1,2,...},{...}}
    if m:
        return len(m.group(1).split(","))
    return 2


@dataclasses.dataclass
class CollectiveStats:
    ops: dict[str, int]
    link_bytes: float                # per device, ring-model
    result_bytes: float              # raw sum of collective result sizes
    by_op: dict[str, float]

    def to_json(self):
        return dataclasses.asdict(self)


def collective_stats(hlo_text: str) -> CollectiveStats:
    ops: dict[str, int] = {}
    by_op: dict[str, float] = {}
    link = 0.0
    raw = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        nbytes = _shape_bytes(type_str)
        g = _group_size(line)
        if op == "all-reduce":
            moved = 2.0 * nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            moved = float(nbytes) * (g - 1)
        elif op == "collective-permute":
            moved = float(nbytes)
        else:                          # all-gather / all-to-all
            moved = float(nbytes) * (g - 1) / g
        ops[op] = ops.get(op, 0) + 1
        by_op[op] = by_op.get(op, 0.0) + moved
        link += moved
        raw += nbytes
    return CollectiveStats(ops=ops, link_bytes=link, result_bytes=raw,
                           by_op=by_op)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    link_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float               # 6ND (train) / 2ND (serve), global
    useful_flops_ratio: float        # model_flops/chips / hlo_flops
    roofline_fraction: float         # ideal_compute / max(all terms)
    collectives: dict[str, Any]
    memory_analysis: dict[str, float]

    def to_json(self):
        return dataclasses.asdict(self)


def analyze(compiled, *, n_chips: int, model_flops: float) -> Roofline:
    from . import hlo_cost as hc
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    cost = hc.hlo_cost(txt)           # loop-aware (see hlo_cost.py docstring)
    flops = cost.flops
    nbytes = cost.bytes
    cs = CollectiveStats(
        ops=collective_stats(txt).ops, link_bytes=cost.link_bytes,
        result_bytes=0.0, by_op=cost.coll_by_op)
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = nbytes / HBM_BW
    coll_s = cs.link_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    bottleneck = max(terms, key=terms.get)  # type: ignore[arg-type]
    ideal = (model_flops / n_chips) / PEAK_FLOPS_BF16
    worst = max(terms.values())
    mem = compiled.memory_analysis()
    mem_d = {
        "argument_bytes": float(mem.argument_size_in_bytes),
        "output_bytes": float(mem.output_size_in_bytes),
        "temp_bytes": float(mem.temp_size_in_bytes),
        "alias_bytes": float(mem.alias_size_in_bytes),
        "peak_bytes": float(mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes
                            - mem.alias_size_in_bytes),
    }
    return Roofline(
        flops_per_device=flops, bytes_per_device=nbytes,
        link_bytes_per_device=cs.link_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        bottleneck=bottleneck, model_flops=model_flops,
        useful_flops_ratio=(model_flops / n_chips) / flops if flops else 0.0,
        roofline_fraction=ideal / worst if worst else 0.0,
        collectives={**cs.to_json(),
                     "loops": [list(x) for x in cost.loops],
                     "cost_analysis_flops_once": float(ca.get("flops", 0.0)),
                     "cost_analysis_bytes_once":
                         float(ca.get("bytes accessed", 0.0))},
        memory_analysis=mem_d)


def model_flops_estimate(cfg, batch: int, seq: int, kind: str) -> float:
    """MODEL_FLOPS: 6*N*D (train) or 2*N*D (forward), N = active params.

    For decode, D = batch tokens (one step) and attention adds
    2 * layers * kv_bytes-equivalent reads -- we report the matmul-model
    number (the standard MFU convention) and let useful_flops_ratio carry
    the gap.
    """
    n = cfg.n_active_params
    tokens = batch * seq
    if kind == "train":
        return 6.0 * n * tokens
    if kind == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * batch            # decode: one token per sequence
