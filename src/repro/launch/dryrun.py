import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, record memory/cost/collective analyses.

This is the proof that the distribution config is coherent without real
hardware: any sharding mismatch, OOM-at-compile, or unsupported
collective fails here.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b \
        --shape train_4k --mesh multi_pod
Results land in results/dryrun/<mesh>/<arch>__<shape>.json.
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS,
    SHAPES,
    get_config,
    input_specs,
    shape_applicable,
)
from repro.distributed.rules import adjust_batch_rule, make_rules  # noqa: E402
from repro.distributed.sharding import param_specs, use_rules, logical_spec  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.model import (  # noqa: E402
    cache_logical_axes,
    count_active_params,
    count_flop_params,
    decode_step,
    init_params,
    param_logical_axes,
    prefill,
)
from repro.optim.adamw import adamw  # noqa: E402
from repro.roofline.analysis import collective_bytes_from_hlo, roofline_report  # noqa: E402
from repro.roofline.hlo_parse import loop_aware_costs  # noqa: E402
from repro.training.step import make_train_step  # noqa: E402

from jax.sharding import PartitionSpec as P  # noqa: E402


RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _batch_specs(cfg, shape, rules):
    """PartitionSpecs for the input batch pytree."""
    b = rules["batch"]
    if shape.kind == "train":
        specs = {"tokens": P(b, None), "targets": P(b, None)}
        if cfg.family == "vlm":
            specs["patches"] = P(b, None, None)
        if cfg.family == "encdec":
            specs["frames"] = P(b, None, None)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": P(b, None)}
        if cfg.family == "vlm":
            specs["patches"] = P(b, None, None)
        if cfg.family == "encdec":
            specs["frames"] = P(b, None, None)
        return specs
    # decode
    cache_spec = param_specs(cache_logical_axes(cfg), rules)
    return {"token": P(b, None), "pos": P(), "cache": cache_spec}


def _abstract_state(cfg, optimizer):
    def build():
        params = init_params(cfg, jax.random.PRNGKey(0))
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    return jax.eval_shape(build)


def _state_specs(cfg, rules):
    p_axes = param_logical_axes(cfg)
    p_specs = param_specs(p_axes, rules)
    return {
        "params": p_specs,
        "opt_state": {
            "mu": p_specs,
            "nu": p_specs,
            "step": P(),
        },
        "step": P(),
    }


def run_cell(arch: str, shape_name: str, mesh_name: str, *, verbose: bool = True,
             cfg_overrides: dict | None = None,
             attn_batch_layout: bool = False) -> dict:
    import dataclasses as _dc

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    multi_pod = mesh_name == "multi_pod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    job = {"train": "train", "prefill": "prefill", "decode": "decode"}[shape.kind]
    rules = make_rules(cfg, multi_pod=multi_pod, job=job)
    rules = adjust_batch_rule(rules, shape.global_batch, multi_pod)
    if attn_batch_layout:
        from repro.distributed.rules import apply_attn_batch_layout

        rules = apply_attn_batch_layout(
            rules, cfg, shape.global_batch, multi_pod=multi_pod)

    t0 = time.time()
    with jax.set_mesh(mesh), use_rules(rules):
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            optimizer = adamw(3e-4)
            step_fn = make_train_step(cfg, optimizer)
            state_abs = _abstract_state(cfg, optimizer)
            state_specs = _state_specs(cfg, rules)
            bspecs = _batch_specs(cfg, shape, rules)
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_specs, bspecs),
                out_shardings=(state_specs, P()),
            ).lower(state_abs, specs)
            n_tokens = shape.global_batch * shape.seq_len
            train = True
        elif shape.kind == "prefill":
            params_abs = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            p_specs = param_specs(param_logical_axes(cfg), rules)
            bspecs = _batch_specs(cfg, shape, rules)
            dec_rules = adjust_batch_rule(
                make_rules(cfg, multi_pod=multi_pod, job="decode"),
                shape.global_batch, multi_pod)
            cache_out = param_specs(cache_logical_axes(cfg), dec_rules)
            fn = lambda params, batch: prefill(  # noqa: E731
                params, batch, cfg, max_seq=shape.seq_len)
            lowered = jax.jit(
                fn,
                in_shardings=(p_specs, bspecs),
                out_shardings=(P(rules["batch"], "model"), cache_out),
            ).lower(params_abs, specs)
            n_tokens = shape.global_batch * shape.seq_len
            train = False
        else:  # decode
            params_abs = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            p_specs = param_specs(param_logical_axes(cfg), rules)
            bspecs = _batch_specs(cfg, shape, rules)
            fn = lambda params, token, pos, cache: decode_step(  # noqa: E731
                params, token, pos, cache, cfg)
            lowered = jax.jit(
                fn,
                in_shardings=(p_specs, bspecs["token"], bspecs["pos"],
                              bspecs["cache"]),
                out_shardings=(P(rules["batch"], "model"), bspecs["cache"]),
            ).lower(params_abs, specs["token"], specs["pos"], specs["cache"])
            # decode processes one token per sequence
            n_tokens = shape.global_batch
            train = False

        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware parse: scan bodies multiplied by trip count (XLA's flat
    # cost_analysis counts while bodies once)
    parsed = loop_aware_costs(hlo)
    coll = {k: float(v) for k, v in parsed["collectives"].items()}
    coll_flat = collective_bytes_from_hlo(hlo)

    params_abs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n_active = count_active_params(params_abs, cfg)
    n_flop = count_flop_params(params_abs, cfg)
    mf = (6.0 if train else 2.0) * n_flop * n_tokens

    flops = float(parsed["flops"])
    bytes_acc = float(parsed["bytes"])
    roof = roofline_report(
        flops=flops,
        bytes_accessed=bytes_acc,
        collective_bytes=float(coll["total"]),
        n_chips=n_chips,
        model_flops=mf,
    )
    roof["xla_flat_flops"] = float(cost.get("flops", 0.0))
    roof["xla_flat_bytes"] = float(cost.get("bytes accessed", 0.0))
    roof["flat_collective_b"] = int(coll_flat["total"])

    def mem_field(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "n_chips": n_chips,
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_size_b": mem_field("argument_size_in_bytes"),
            "output_size_b": mem_field("output_size_in_bytes"),
            "temp_size_b": mem_field("temp_size_in_bytes"),
            "generated_code_size_b": mem_field("generated_code_size_in_bytes"),
        },
        "cost": {"flops": flops, "bytes_accessed": bytes_acc},
        "collectives": coll,
        "roofline": roof,
        "active_params": n_active,
    }
    if verbose:
        print(json.dumps(result, indent=None))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single_pod", "multi_pod"],
                    default="single_pod")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) for the chosen mesh(es)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="disable the adopted §Perf optimizations "
                         "(attention batch layout)")
    ap.add_argument("--tag", default="",
                    help="suffix for the results directory")
    args = ap.parse_args()

    meshes = (["single_pod", "multi_pod"] if args.both_meshes
              else [args.mesh])
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    failures = []
    for mesh_name in meshes:
        outdir = RESULTS_DIR / (mesh_name + args.tag)
        outdir.mkdir(parents=True, exist_ok=True)
        for arch in archs:
            for shape_name in shapes:
                tag = f"{mesh_name}{args.tag}/{arch}__{shape_name}"
                out = outdir / f"{arch}__{shape_name}.json"
                try:
                    res = run_cell(arch, shape_name, mesh_name, verbose=False,
                                   attn_batch_layout=not args.baseline)
                except Exception as e:  # noqa: BLE001
                    res = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": "error", "error": repr(e),
                        "traceback": traceback.format_exc(),
                    }
                    failures.append(tag)
                out.write_text(json.dumps(res, indent=2))
                status = res["status"]
                extra = ""
                if status == "ok":
                    r = res["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" bound={r['step_time_lower_bound_s']:.4f}s"
                             f" compile={res['compile_s']}s")
                elif status == "skipped":
                    extra = f" ({res['reason'][:60]})"
                print(f"[{status:7s}] {tag}{extra}", flush=True)

    if failures:
        print(f"\nFAILED cells: {failures}")
        raise SystemExit(1)
    print("\nDRY-RUN PASSED")


if __name__ == "__main__":
    main()
