"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × shape × mesh) cell, all in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOP/s          (197 TF bf16, v5e)
  memory     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
  collective = collective_bytes_per_device / link_bw       (~50 GB/s/link)

``cost_analysis()`` of an SPMD-partitioned module reports PER-DEVICE flops
and bytes (the module IS the per-device program). Collective bytes are not
in cost_analysis — ``collective_stats`` regex-parses the compiled HLO and
sums result-shape bytes of every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute (async -start forms included, -done skipped).
All-reduce is counted 2× (ring = reduce-scatter + all-gather).

The report also carries MODEL_FLOPS / HLO_FLOPs — the "useful compute"
ratio that exposes remat/dispatch waste.
"""
from __future__ import annotations

import re
from typing import Any

# Hardware constants for the roofline terms (TPU v5e) — the ONE source of
# truth. The launch layer (mesh policy, dry-run HBM check) re-exports these
# from here so the roofline table and the dry-run report can never disagree
# on what a chip is.
PEAK_FLOPS_BF16 = 197e12       # FLOP/s per chip, bf16
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
CHIP_HBM_BYTES = 16 * 1024**3  # v5e: 16 GiB


def num_chips(mesh) -> int:
    """Total devices of a mesh — the per-device divisor of every roofline
    and capacity figure (dry-run report, sharded-index sizing)."""
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict[str, Any]:
    """Per-op-type byte totals + overall collective_bytes (per device)."""
    per_op: dict[str, int] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, op, _start = m.group(1), m.group(2), m.group(3)
        b = _shape_bytes(type_str)
        if op == "all-reduce":
            b *= 2  # ring all-reduce = reduce-scatter + all-gather
        per_op[op] = per_op.get(op, 0) + b
        counts[op] = counts.get(op, 0) + 1
    return {
        "bytes_by_op": per_op,
        "counts_by_op": counts,
        "collective_bytes": sum(per_op.values()),
    }


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
) -> dict[str, float]:
    compute = flops_per_device / PEAK_FLOPS_BF16
    memory = bytes_per_device / HBM_BW
    collective = collective_bytes_per_device / ICI_BW
    dominant = max(
        ("compute", compute), ("memory", memory), ("collective", collective),
        key=lambda kv: kv[1],
    )[0]
    bound = max(compute, memory, collective)
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
        # fraction of the bound spent on useful compute — the roofline score
        "roofline_fraction": (compute / bound) if bound > 0 else 0.0,
    }


def analyze(compiled, lowered=None, model_flops_total: float | None = None,
            n_chips: int = 1, loop_trips: float = 1.0) -> dict[str, Any]:
    """Full per-cell report from a compiled executable.

    ``loop_trips``: XLA's cost_analysis counts each while-loop body ONCE, so
    scan-dominated programs under-report flops/bytes by the trip count
    (measured ~600× on the 96-layer × 16-microbatch train cell). The cell
    builder supplies the known trip product of the dominant loop nest
    (layers × microbatches); out-of-loop contributions are ≤ a few % for
    scan-dominated cells, so scaling the totals is a ≲10% approximation —
    recorded here rather than hidden.
    """
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0)) * loop_trips
    byts = float(cost.get("bytes accessed", 0.0)) * loop_trips
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    coll = {
        "bytes_by_op": coll["bytes_by_op"],
        "counts_by_op": coll["counts_by_op"],
        "collective_bytes": coll["collective_bytes"] * loop_trips,
    }
    mem = compiled.memory_analysis()
    out = {
        "flops_per_device": flops,
        "bytes_per_device": byts,
        "loop_trips": loop_trips,
        **coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
            "hbm_limit": CHIP_HBM_BYTES,
        },
        **roofline_terms(flops, byts, coll["collective_bytes"]),
    }
    if model_flops_total is not None and flops > 0:
        out["model_flops_total"] = model_flops_total
        out["model_flops_per_device"] = model_flops_total / n_chips
        out["useful_compute_ratio"] = (model_flops_total / n_chips) / flops
    return out

# ---------------------------------------------------------------------------
# kernel-level predicted-vs-measured (PR 7)
# ---------------------------------------------------------------------------

def kernel_predicted(flops: float, bytes_moved: float,
                     collective_bytes: float = 0.0) -> dict[str, Any]:
    """Roofline bound for a single kernel launch, in µs.

    Kernels (unlike train cells) are small enough to model their traffic in
    closed form, so the benchmark harness computes ``bytes_moved`` from the
    grid schedule (see :func:`adc_scan_traffic`) and books this prediction
    next to the measured wall-clock — the "predicted vs measured" entry every
    kernel section of ``benchmarks/kernels_micro.py`` must carry.
    """
    t = roofline_terms(flops, bytes_moved, collective_bytes)
    return {
        "predicted_us": max(t["compute_s"], t["memory_s"], t["collective_s"]) * 1e6,
        "dominant": t["dominant"],
        "flops": flops,
        "bytes": bytes_moved,
    }


def adc_scan_traffic(b: int, Dp: int, K: int, steps: int, block: int,
                     lut_dtype: str = "float32", code_bytes: int = 1,
                     luts_per_step: int = 1) -> float:
    """Modeled HBM traffic (bytes) of one ADC scan launch.

    Per grid step the scan DMAs ``luts_per_step`` LUT rows (the whole
    (b, Dp, K) table for the flat scan, one query's row for the selected-block
    scan), one (block, Dp) code tile, and writes one (b, block) f32 score
    tile; ``steps`` is the number of scheduled grid steps. Integer LUT packs
    move 1 byte/entry plus the f32 (Dp, 2) scale/offset sidecar — the per-step
    LUT stream shrinks 4×, which is where the ≥2× total-bytes win of the int8
    pack comes from (codes are uint8 for K ≤ 256, so the corpus-side stream
    is already thin).
    """
    lut_entry = 4 if lut_dtype == "float32" else 1
    scales = 0 if lut_dtype == "float32" else Dp * 2 * 4
    lut_row = luts_per_step * (Dp * K * lut_entry + scales)
    codes_blk = block * Dp * code_bytes
    out_blk = b * block * 4
    return float(steps) * (lut_row + codes_blk + out_blk)


def fused_lut_traffic(b: int, n: int, Dp: int, K: int, sub: int) -> float:
    """Modeled HBM traffic (bytes) of one fused rotation-aware LUT build:
    queries (b, n) + delta product (n, n) + flat codebooks (Dp, K, sub) +
    one-hot column map (Dp, n) in, (b, Dp, K) f32 table out."""
    return 4.0 * (b * n + n * n + Dp * K * sub + Dp * n + b * Dp * K)
