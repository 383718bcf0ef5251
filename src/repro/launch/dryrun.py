import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a virtual CPU mesh: never take an accelerator, so on a TPU host no cell
# (nor the --all children, which re-run this module) competes for the chip
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init).

_DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all          # subprocess per cell, resumable

Each cell writes results/dryrun/{arch}_{shape}_{mesh}[_tag].json with
memory_analysis, cost_analysis, collective wire bytes, and roofline terms.
Sharding failures / OOM-at-compile are bugs — they land in the JSON as
"error" and fail the sweep summary.
"""

import argparse
import json
import pathlib
import subprocess
import sys
import time

import jax

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..models import build_model
from ..optim import AdamW, OptState
from ..runtime import TrainState, init_train_state, make_rules, make_train_step
from .cost_model import COST_KEYS, cost_variants, solve_costs
from .mesh import make_production_mesh
from .roofline import parse_collective_bytes, roofline_terms
from .specs import input_specs

RESULTS = pathlib.Path("results/dryrun")


def _preset_for(shape) -> str:
    if shape.name == "long_500k":
        return "long"
    if shape.kind == "decode":
        return "decode"
    return "train"


def _compile_cell(cfg, shape, mesh, rules, remat: str, microbatches: int):
    """Lower + compile one (config, shape) on a mesh. Returns compiled."""
    model = build_model(cfg)
    batch_abs, batch_axes = input_specs(cfg, shape, model)
    batch_shardings = rules.tree_shardings(batch_abs, batch_axes)

    if shape.kind == "train":
        opt = AdamW(lr=3e-4)
        step = make_train_step(model, opt, rules=rules, remat=remat,
                               microbatches=microbatches)
        state_abs = jax.eval_shape(
            lambda k: init_train_state(model, k, opt), jax.random.PRNGKey(0))
        p_sh = rules.tree_shardings(model.abstract(), model.axes())
        state_sh = TrainState(
            params=p_sh,
            opt=OptState(step=rules.named((), ()), m=p_sh, v=p_sh),
            err=None)
        jf = jax.jit(step, in_shardings=(state_sh, batch_shardings),
                     donate_argnums=(0,))
        return jf.lower(state_abs, batch_abs).compile(), model
    p_abs = model.abstract()
    p_sh = rules.tree_shardings(p_abs, model.axes())
    if shape.kind == "prefill":
        def fn(p, b):
            return model.prefill(p, b, rules=rules)
        jf = jax.jit(fn, in_shardings=(p_sh, batch_shardings))
    else:
        def fn(p, b):
            return model.decode(p, b, rules=rules)
        jf = jax.jit(fn, in_shardings=(p_sh, batch_shardings),
                     donate_argnums=(1,))
    return jf.lower(p_abs, batch_abs).compile(), model


def _extract_costs(compiled, n_dev) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = parse_collective_bytes(compiled.as_text(), n_dev)
    vals = {k: float(cost.get(k, 0.0)) for k in COST_KEYS}
    for kind, b in coll["by_kind"].items():
        vals[f"wire:{kind}"] = b
    vals["wire:total"] = coll["total_wire_bytes"]
    for kind, c in coll["counts"].items():
        vals[f"count:{kind}"] = float(c)
    return vals


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    remat: str = "full",
    microbatches: int = 1,
    overrides: dict | None = None,
    return_artifacts: bool = False,
    config_overrides: dict | None = None,
):
    """Lower + compile one cell; returns the result record (and artifacts).

    Two kinds of compiles happen:
      1. the FULL-depth compile (scanned stacks) → memory_analysis + proof
         that the production sharding lowers and fits;
      2. 2–3 reduced-depth UNROLLED cost compiles → exact FLOPs / bytes /
         collective wire bytes via affine depth extrapolation
         (launch/cost_model.py — XLA counts while bodies once).
    """
    cfg = get_config(arch)
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": True, "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(len(mesh.devices.reshape(-1)))
    rules = make_rules(mesh, _preset_for(shape), overrides)

    t0 = time.time()
    compiled, model = _compile_cell(cfg, shape, mesh, rules, remat,
                                    microbatches)
    t_full = time.time() - t0
    ma = compiled.memory_analysis()

    # cost compiles (reduced depth, unrolled, single-chunk)
    t0 = time.time()
    variants, solve = cost_variants(cfg, shape.seq_len, shape.kind)
    vals = []
    for vcfg in variants:
        vc, _ = _compile_cell(vcfg, shape, mesh, rules, remat, 1)
        vals.append(_extract_costs(vc, n_dev))
    corrected = solve_costs(vals, solve)
    t_cost = time.time() - t0

    cost = {"flops": corrected["flops"],
            "bytes accessed": corrected["bytes accessed"],
            "transcendentals": corrected.get("transcendentals", 0.0)}
    coll = {"by_kind": {k.split(":", 1)[1]: v for k, v in corrected.items()
                        if k.startswith("wire:") and k != "wire:total"},
            "counts": {k.split(":", 1)[1]: v for k, v in corrected.items()
                       if k.startswith("count:")},
            "total_wire_bytes": corrected["wire:total"]}
    terms = roofline_terms(cost, coll, n_dev, model, shape)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "remat": remat, "microbatches": microbatches,
        "overrides": overrides or {},
        "config_overrides": config_overrides or {},
        "compile_s": round(t_full, 1), "cost_compiles_s": round(t_cost, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_est_bytes": (ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes),
        },
        "cost": cost,
        "collectives": coll,
        "roofline": terms,
    }
    if return_artifacts:
        return rec, compiled, model
    return rec


def run_one(args) -> int:
    RESULTS.mkdir(parents=True, exist_ok=True)
    tag = f"_{args.tag}" if args.tag else ""
    out = RESULTS / f"{args.arch}_{args.shape}_{args.mesh}{tag}.json"
    try:
        rec = lower_cell(args.arch, args.shape, args.mesh == "multi",
                         remat=args.remat, microbatches=args.microbatches,
                         overrides=json.loads(args.overrides)
                         if args.overrides else None,
                         config_overrides=json.loads(args.config_overrides)
                         if args.config_overrides else None)
    except Exception as e:  # noqa: BLE001 — recorded, sweep summary fails
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "error": f"{type(e).__name__}: {e}"}
    out.write_text(json.dumps(rec, indent=1, default=str))
    if rec.get("error"):
        print(f"FAIL {out.name}: {rec['error'][:300]}")
        return 1
    if rec.get("skipped"):
        print(f"SKIP {out.name}: {rec['reason']}")
        return 0
    r = rec["roofline"]
    print(f"OK   {out.name} compile={rec['compile_s']}s "
          f"mem={rec['memory']['peak_est_bytes']/2**30:.2f}GiB/dev "
          f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
          f"coll={r['collective_s']:.4f}s -> {r['bottleneck']}")
    return 0


def run_all(args) -> int:
    RESULTS.mkdir(parents=True, exist_ok=True)
    cells = [(a, s, m)
             for a in ARCHS for s in SHAPES for m in ("single", "multi")]
    fails = 0
    for arch, shape, mesh_kind in cells:
        out = RESULTS / f"{arch}_{shape}_{mesh_kind}.json"
        if out.exists() and not args.force:
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
               "--remat", args.remat]
        print(">>", " ".join(cmd[3:]), flush=True)
        try:
            proc = subprocess.run(cmd, timeout=args.cell_timeout)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            out.write_text(json.dumps(
                {"arch": arch, "shape": shape, "mesh": mesh_kind,
                 "error": f"compile timeout > {args.cell_timeout}s"}))
            print(f"FAIL {out.name}: timeout", flush=True)
            rc = 1
        fails += int(rc != 0)
    print(f"sweep done, {fails} failures")
    return int(fails > 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--overrides", default="",
                    help="JSON dict of sharding-rule overrides")
    ap.add_argument("--config-overrides", default="",
                    help="JSON dict of ModelConfig field overrides")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--cell-timeout", type=int, default=3600)
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    assert args.arch and args.shape, "--arch/--shape required without --all"
    sys.exit(run_one(args))


if __name__ == "__main__":
    main()
