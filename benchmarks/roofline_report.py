"""Render the §Roofline table from results/dryrun/*.json.

    PYTHONPATH=src:. python -m benchmarks.roofline_report [--mesh single_pod]

Per (arch x shape): the three roofline terms (seconds/step), the
dominant term, MODEL_FLOPS/HLO_FLOPs, the MFU upper bound, and a
one-line mitigation note for the dominant term.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.common import enable_compile_cache


RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"

MITIGATION = {
    ("compute",): "raise arithmetic intensity (fuse, larger microbatch)",
    ("memory",): "cut HBM spills: kernel-fused attention (scores in VMEM), "
                 "bf16 intermediates, remat policy",
    ("collective",): "re-shard to remove gathers (attention layout, EP vs TP), "
                     "overlap collectives with compute",
}


def note_for(row: dict) -> str:
    arch, shape = row["arch"], row["shape"]
    dom = row["roofline"]["dominant"]
    if arch == "yi_34b" and dom in ("memory", "collective"):
        return ("56 heads don't divide the 16-way model axis -> head_dim "
                "sharding psum/AG storm in flash; fix: batch-(data,model) "
                "attention layout")
    if "moe" in arch and dom == "collective":
        return "EP token exchange dominates; compare TP expert sharding"
    if shape.startswith("decode") and dom == "memory":
        return "weight+KV reads per token; raise decode batch / quantize KV"
    if shape == "long_500k":
        return "SSM state + shared-attn KV reads; O(1) in seq per token"
    return MITIGATION[(dom,)]


def load(mesh: str) -> list[dict]:
    rows = []
    for f in sorted((RESULTS / mesh).glob("*.json")):
        rows.append(json.loads(f.read_text()))
    return rows


def render(mesh: str) -> str:
    rows = load(mesh)
    out = [
        f"### Roofline — {mesh} ({'512' if mesh == 'multi_pod' else '256'} chips, "
        "TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link)",
        "",
        "| arch | shape | compute s | memory s | collective s | dominant |"
        " MODEL/HLO flops | MFU ub | note |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows.sort(key=lambda r: (r["arch"], order.get(r["shape"], 9)))
    for r in rows:
        if r["status"] == "skipped":
            out.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | skipped |"
                f" — | — | full-attention arch: long_500k n/a |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | ERROR |||||||")
            continue
        rf = r["roofline"]
        out.append(
            f"| {r['arch']} | {r['shape']} "
            f"| {rf['compute_s']:.3g} | {rf['memory_s']:.3g} "
            f"| {rf['collective_s']:.3g} | **{rf['dominant']}** "
            f"| {rf['useful_flops_ratio']:.2f} "
            f"| {rf['mfu_upper_bound']*100:.1f}% "
            f"| {note_for(r)} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    args = ap.parse_args()
    enable_compile_cache()
    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])
    for m in meshes:
        print(render(m))
        print()


if __name__ == "__main__":
    main()
