"""§Perf hillclimb harness: hypothesis -> change -> re-lower -> measure.

Each experiment re-runs a dry-run cell with one change and records the
three roofline terms next to the baseline.  Results append to
results/perf/<name>.json; EXPERIMENTS.md §Perf narrates them.

    PYTHONPATH=src:. python -m benchmarks.perf_iterations --exp yi_attn_layout
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.common import enable_compile_cache


RESULTS = Path(__file__).resolve().parents[1] / "results" / "perf"


EXPERIMENTS = {
    # (arch, shape, mesh, kwargs)
    "yi_attn_layout": dict(
        arch="yi_34b", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "yi's 56 heads don't divide the 16-way model axis, so the "
            "baseline shards head_dim; every flash-block einsum then "
            "contracts over a sharded dim -> SPMD inserts AG/psum per "
            "block pair x 60 layers (memory 301s / collective 299s). "
            "Re-sharding attention over batch=(data x model) makes all "
            "attention local; predicted: collective term drops >10x, "
            "memory term approaches qwen3-like scale (x7 model size)."),
        kwargs=dict(attn_batch_layout=True),
    ),
    "yi_attn_layout_prefill": dict(
        arch="yi_34b", shape="prefill_32k", mesh="single_pod",
        hypothesis=(
            "same layout lever on the prefill cell (batch 32 < 256 -> "
            "the lever must no-op and match baseline; negative control)."),
        kwargs=dict(attn_batch_layout=True),
    ),
    "moe_tp_vs_ep": dict(
        arch="granite_moe_1b_a400m", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "the EP dispatch gathers the full token set across the model "
            "axis every layer (collective 8.5s dominates). TP expert "
            "sharding (d_ff=512 -> 32/device) keeps tokens local; "
            "predicted: collective drops to FSDP-AG/AR scale (~10x), "
            "at no flop cost (dispatch einsums unchanged)."),
        kwargs=dict(cfg_overrides={"moe_parallel": "tp"}),
    ),
    "moe_grouped_dispatch": dict(
        arch="granite_moe_1b_a400m", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "flat EP sorts/gathers the GLOBAL token set -> SPMD "
            "all-gathers every token across the model axis per layer. "
            "Group-local dispatch (16 groups on the data axis, Switch-"
            "style per-device capacity) keeps routing local; only the "
            "expert-sliced block and the combine psum cross the mesh. "
            "Predicted: collective term -5..20x."),
        kwargs=dict(cfg_overrides={"dispatch_groups": 16}),
    ),
    "yi_attn_layout_v2": dict(
        arch="yi_34b", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "iteration 2: v1 left a 117s collective term traced to an "
            "85.9 GB replicated all-gather of the f32 d_ff hidden in "
            "the MLP backward — the partitioner resolving the attn-"
            "layout mismatch inside the MLP. Pinning the residual to "
            "batch='data' at the attention/MLP boundary forces the "
            "cheap (B,S,d) reshard instead. Predicted: collective "
            "-10x+, memory also drops (no replicated hidden)."),
        kwargs=dict(attn_batch_layout=True),
    ),
    "yi_attn_layout_v3": dict(
        arch="yi_34b", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "iteration 3: v2's remaining 53.8s collective traces to a "
            "30 GB replicated all-gather of the f32 (B,S,56,128) "
            "attention cotangent — XLA's 'involuntary full remat' when "
            "resharding 4D projections. Entering the attention layout "
            "on the 3D hidden BEFORE the q/k/v einsums makes the "
            "reshard a cheap (B,S,d) all-to-all. Predicted: collective "
            "-3x+ again."),
        kwargs=dict(attn_batch_layout=True),
    ),
    "mixtral_p_bf16": dict(
        arch="mixtral_8x22b", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "flash-block probability tiles spill to HBM in f32 "
            "(XLA does not fuse matmul->softmax->matmul). Casting the "
            "tile to bf16 before the PV matmul halves that spill; "
            "predicted: memory term -15..30% (attention share of "
            "traffic), flops unchanged, <0.1% accuracy cost."),
        kwargs=dict(cfg_overrides={"attn_p_bf16": True}),
    ),
    "qwen3_p_bf16": dict(
        arch="qwen3_8b", shape="train_4k", mesh="single_pod",
        hypothesis="same bf16-tile lever on the dense 8B cell.",
        kwargs=dict(cfg_overrides={"attn_p_bf16": True}),
    ),
    "mixtral_grouped_dispatch": dict(
        arch="mixtral_8x22b", shape="prefill_32k", mesh="single_pod",
        hypothesis=(
            "mixtral prefill is collective-bound (41.8s) for the same "
            "reason granite-moe was: the TP-MoE dispatch still sorts/"
            "gathers the GLOBAL 1M-token set. Group-local dispatch (16 "
            "groups on data) should cut the dispatch collectives as it "
            "did for granite-moe. Predicted: collective -30%+."),
        kwargs=dict(cfg_overrides={"dispatch_groups": 16}),
    ),
    "mixtral_grouped_train": dict(
        arch="mixtral_8x22b", shape="train_4k", mesh="single_pod",
        hypothesis="same grouped-dispatch lever on the train cell "
                   "(memory-dominant there; collective is secondary).",
        kwargs=dict(cfg_overrides={"dispatch_groups": 16}),
    ),
    "qwen3_remat_dots": dict(
        arch="qwen3_8b", shape="train_4k", mesh="single_pod",
        hypothesis=(
            "full-block remat recomputes the forward (incl. flash) in "
            "backward: ~1.33x flops and a second pass of attention "
            "spill. Saving dot outputs (checkpoint_dots_with_no_batch_"
            "dims) trades live memory for less recompute; predicted: "
            "compute -20%, memory term -10..20%, temp bytes +."),
        kwargs=dict(cfg_overrides={"remat_policy": "dots"}),
    ),
    "mixtral_both": dict(
        arch="mixtral_8x22b", shape="train_4k", mesh="single_pod",
        hypothesis="bf16 tiles + attn batch layout combined (SWA arch; "
                   "heads divide, so layout no-ops — isolates bf16).",
        kwargs=dict(cfg_overrides={"attn_p_bf16": True},
                    attn_batch_layout=True),
    ),
}


def run(exp_name: str) -> dict:
    from repro.launch.dryrun import run_cell

    exp = EXPERIMENTS[exp_name]
    base = run_cell(exp["arch"], exp["shape"], exp["mesh"], verbose=False)
    new = run_cell(exp["arch"], exp["shape"], exp["mesh"], verbose=False,
                   **exp["kwargs"])

    def terms(r):
        if r["status"] != "ok":
            return {"status": r["status"], "error": r.get("error")}
        rf = r["roofline"]
        return {
            "compute_s": rf["compute_s"],
            "memory_s": rf["memory_s"],
            "collective_s": rf["collective_s"],
            "dominant": rf["dominant"],
            "bound_s": rf["step_time_lower_bound_s"],
            "mfu_ub": rf["mfu_upper_bound"],
        }

    b, n = terms(base), terms(new)
    result = {
        "experiment": exp_name,
        "arch": exp["arch"], "shape": exp["shape"], "mesh": exp["mesh"],
        "hypothesis": exp["hypothesis"],
        "baseline": b,
        "change": n,
    }
    if "bound_s" in b and "bound_s" in n:
        result["bound_speedup"] = b["bound_s"] / max(n["bound_s"], 1e-12)
        dom = b["dominant"] + "_s"
        result["dominant_term_speedup"] = b[dom] / max(n[dom], 1e-12)
        result["verdict"] = (
            "confirmed" if result["dominant_term_speedup"] > 1.05 else
            ("neutral" if result["dominant_term_speedup"] > 0.95
             else "refuted"))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default="all",
                    help=f"one of {list(EXPERIMENTS)} or 'all'")
    args = ap.parse_args()
    enable_compile_cache()
    RESULTS.mkdir(parents=True, exist_ok=True)
    names = list(EXPERIMENTS) if args.exp == "all" else [args.exp]
    for name in names:
        res = run(name)
        (RESULTS / f"{name}.json").write_text(json.dumps(res, indent=2))
        b, n = res["baseline"], res["change"]
        print(f"== {name} [{res.get('verdict', '?')}] ==")
        if "bound_s" in b:
            print(f"  baseline: comp {b['compute_s']:.3g} mem {b['memory_s']:.3g} "
                  f"coll {b['collective_s']:.3g} bound {b['bound_s']:.3g}")
            print(f"  change  : comp {n['compute_s']:.3g} mem {n['memory_s']:.3g} "
                  f"coll {n['collective_s']:.3g} bound {n['bound_s']:.3g}")
            print(f"  dominant-term speedup {res['dominant_term_speedup']:.2f}x, "
                  f"bound speedup {res['bound_speedup']:.2f}x", flush=True)


if __name__ == "__main__":
    main()
