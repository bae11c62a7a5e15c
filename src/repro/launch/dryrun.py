import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the production
mesh is built from 512 placeholder CPU devices, every cell's step function is
lowered with fully-sharded ShapeDtypeStructs, compiled by the SPMD
partitioner, and the compiled artifact is mined for the roofline terms
(FLOPs / bytes from cost_analysis, collective operand bytes from the
post-SPMD HLO). Results land in a JSON consumed by benchmarks/roofline.py
and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results/dryrun.json
"""
import argparse
import json
import math
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell, per_device_bytes
from repro.utils import hlo as hlo_utils

from repro.utils.machine import machine_profile

# the dry run stands 512 placeholder CPU devices in for v5e pods
# (launch/mesh.py), so its roofline terms use the v5e peaks
# (utils/machine.py; the REPRO_* variables still override)
_PROFILE = None


def _peaks():
    global _PROFILE
    if _PROFILE is None:
        _PROFILE = machine_profile(device_kind="TPU v5 lite")
    return _PROFILE


ACCOUNTING_OVERRIDES = dict(scan_layers=False, microbatches=1,
                            unroll_scans=True)


def accounting_variants(cfg):
    """Reduced-depth variants + a linear combiner for exact-by-extrapolation
    accounting of train/prefill cells (per-layer costs are depth-invariant;
    XLA:CPU cost_analysis cannot see scan trip counts, and fully unrolling
    40-62 layers is too slow on one core — so we compile 2-3 shallow
    *unrolled* variants and extrapolate).
    """
    import dataclasses as dc
    from repro.models.blocks import layer_schedule
    name = cfg.name
    if name.startswith("hymba"):
        v = [dc.replace(cfg, n_layers=4, global_layer_indices=(0,)),
             dc.replace(cfg, n_layers=6, global_layer_indices=(0,)),
             dc.replace(cfg, n_layers=4, global_layer_indices=(0, 1))]
        n_global = len(cfg.global_layer_indices)
        n_swa = cfg.n_layers - n_global

        def combine(m4, m6, m4g2):
            per_swa = (m6 - m4) / 2.0
            d_global = m4g2 - m4
            return m4 + per_swa * (n_swa - 3) + d_global * (n_global - 1)
        return v, combine
    if cfg.moe is not None and cfg.n_dense_layers:      # deepseek: 3 dense + N moe
        v = [dc.replace(cfg, n_layers=cfg.n_dense_layers + 1),
             dc.replace(cfg, n_layers=cfg.n_dense_layers + 2)]
        n_moe = cfg.n_layers - cfg.n_dense_layers

        def combine(m1, m2):
            return m1 + (m2 - m1) * (n_moe - 1)
        return v, combine
    unit = len(cfg.layer_pattern)
    reps, tail = divmod(cfg.n_layers, unit)
    v = [dc.replace(cfg, n_layers=unit), dc.replace(cfg, n_layers=2 * unit)]

    def combine(m1, m2):
        per_unit = m2 - m1
        return m1 + per_unit * (reps - 1) + per_unit * (tail / unit)
    return v, combine


def _measure(cfg, shape, mesh, plan_overrides):
    """Lower+compile one variant; return raw metrics."""
    import contextlib
    import dataclasses as _dc
    t0 = time.time()
    cell = build_cell(cfg, shape, mesh)
    if plan_overrides:
        cell = build_cell(cfg, shape, mesh,
                          plan=_dc.replace(cell.plan, **plan_overrides))
    ctx = contextlib.nullcontext()
    if cell.plan.constrain_activations:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.planner import batch_axes
        from repro.distributed import runtime
        ctx = runtime.activation_sharding(
            NamedSharding(mesh, P(batch_axes(mesh))))
    jitted = jax.jit(cell.step, donate_argnums=cell.donate)
    with ctx:
        lowered = jitted.lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis() or {}
    coll = hlo_utils.collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "coll_bytes": sum(v["bytes"] for v in coll.values()),
        "mem": compiled.memory_analysis(),
    }, cell, t_lower, t_compile


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plan_overrides=None, accounting: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.shape.values())

    if accounting:
        # fully-unrolled lowering: no while loops, so cost_analysis sees every
        # op execution (XLA:CPU does not multiply scan bodies by trip count)
        plan_overrides = {**ACCOUNTING_OVERRIDES, **(plan_overrides or {})}

    t_lower = t_compile = 0.0
    if accounting and shape.kind in ("train", "prefill") and not \
            (plan_overrides or {}).get("no_extrapolate"):
        # depth extrapolation: 2-3 shallow unrolled compiles, combined
        variants, combine = accounting_variants(cfg)
        measures = []
        cell = None
        for vcfg in variants:
            m, cell, tl, tc = _measure(vcfg, shape, mesh, plan_overrides)
            measures.append(m)
            t_lower += tl
            t_compile += tc
        flops = float(combine(*[m["flops"] for m in measures]))
        bytes_acc = float(combine(*[m["bytes"] for m in measures]))
        coll_bytes = float(combine(*[m["coll_bytes"] for m in measures]))
        kinds = set().union(*[m["coll"].keys() for m in measures])
        coll = {k: {f: float(combine(*[m["coll"].get(k, {}).get(f, 0.0)
                                       for m in measures]))
                    for f in ("count", "bytes")} for k in kinds}
        mem = None
    else:
        po = dict(plan_overrides or {})
        po.pop("no_extrapolate", None)
        m, cell, t_lower, t_compile = _measure(cfg, shape, mesh, po)
        flops, bytes_acc = m["flops"], m["bytes"]
        coll, coll_bytes = m["coll"], m["coll_bytes"]
        mem = m["mem"]

    from repro.configs.base import model_flops
    toks = cell.tokens_per_step
    useful = model_flops(cfg, toks) if cell.kind == "train" else \
        2.0 * cfg.active_param_count() * toks

    out = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": n_chips,
        "kind": cell.kind,
        "plan": {k: getattr(cell.plan, k) for k in
                 ("microbatches", "remat", "optimizer", "fsdp", "param_dtype",
                  "logits_chunk", "attn_impl")},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # per-device program costs (SPMD: one device's share)
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "collectives": coll,
        "model_flops_total": useful,
        "hlo_useful_ratio": useful / max(flops * n_chips, 1.0),
        # roofline terms (seconds)
        "t_compute": flops / _peaks().peak_flops,
        "t_memory": bytes_acc / _peaks().hbm_bw,
        "t_collective": coll_bytes / _peaks().link_bw,
        "analytic_state_bytes_per_device": per_device_bytes(mesh, cell.args),
    }
    terms = {"compute": out["t_compute"], "memory": out["t_memory"],
             "collective": out["t_collective"]}
    out["bottleneck"] = max(terms, key=terms.get)
    out["roofline_fraction"] = out["t_compute"] / max(sum(terms.values()), 1e-30)
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            out["mem_" + attr] = getattr(mem, attr, None)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--accounting", action="store_true",
                    help="fully-unrolled lowering for exact cost_analysis")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}:{shape}:{'multi' if mp else 'single'}"
                try:
                    r = run_cell(arch, shape, mp, accounting=args.accounting)
                    r["status"] = "skipped" if "skipped" in r else "ok"
                except Exception as e:  # noqa: BLE001 — record and continue
                    r = {"arch": arch, "shape": shape, "multi_pod": mp,
                         "status": "error", "error": f"{type(e).__name__}: {e}",
                         "trace": traceback.format_exc(limit=6)}
                r["multi_pod"] = mp
                results.append(r)
                if r["status"] == "ok":
                    print(f"OK    {tag:54s} compile={r['compile_s']:7.1f}s "
                          f"bottleneck={r['bottleneck']:10s} "
                          f"roofline={r['roofline_fraction']:.3f}", flush=True)
                elif r["status"] == "skipped":
                    print(f"SKIP  {tag:54s} {r['skipped'][:60]}", flush=True)
                else:
                    print(f"ERROR {tag:54s} {r['error'][:90]}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_err = sum(r["status"] == "error" for r in results)
    print(f"cells: {len(results)}  errors: {n_err}")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
