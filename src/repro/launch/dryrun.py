import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: device count locks at first jax init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step / prefill_step /
serve_step) against abstract ShapeDtypeStruct inputs carrying the production
NamedShardings, compiles it, and records memory_analysis / cost_analysis /
collective inventory to JSON — the roofline table (§Roofline) is built from
these artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import get_config, list_archs
from repro.launch import hlo
from repro.launch.flops import model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _compile_cell(cfg, shape, mesh, multi_pod, step_kw, jit_kw=None):
    fn, abstract_args = build_step(cfg, mesh, shape, multi_pod=multi_pod, **step_kw)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, **(jit_kw or {})).lower(*abstract_args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
    coll = hlo.collective_stats(txt)
    return {
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "collectives": coll,
        "collective_bytes": hlo.total_collective_bytes(coll),
        "hlo_stats": hlo.remat_duplication(txt),
    }


def _probe_cfg(cfg, n_periods: int):
    """Unrolled small-depth config for exact cost_analysis (no while undercount)."""
    c = cfg.replace(num_layers=n_periods * cfg.period, scan_layers=False,
                    unroll_scans=True)
    if cfg.encoder is not None:
        import dataclasses
        c = c.replace(encoder=dataclasses.replace(
            cfg.encoder,
            num_layers=max(1, cfg.encoder.num_layers * n_periods // cfg.num_periods)))
    return c


def _extrapolate(m1: dict, m2: dict, n_periods: int, enc_note: str = "") -> dict:
    """True per-program cost from two unrolled probes: est(T) = m1 + (m2-m1)(T-1)."""
    out = {}
    for k in ("flops", "bytes_accessed", "collective_bytes"):
        per = m2[k] - m1[k]
        out[k + "_est"] = m1[k] + per * (n_periods - 1)
        out[k + "_per_layer"] = per
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             overrides: dict | None = None, tag: str = "",
             skip_probes: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**{k: v for k, v in overrides.items() if hasattr(cfg, k)})
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multi"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "kind": shape.kind, "ok": False}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update(skipped=True, reason=why, ok=True)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    step_kw = {}
    if shape.kind == "train" and overrides:
        if "microbatch" in overrides:
            step_kw["microbatch"] = overrides["microbatch"]
        if "zero1" in overrides:
            step_kw["zero1"] = overrides["zero1"]
    if overrides and overrides.get("rules") == "ep_only":
        # §Perf lever for small-active MoE archs: use the 'model' axis for
        # expert parallelism only; attention/shared-MLP compute replicates
        # (their TP all-reduces were the residual collective term).
        from repro.distributed.sharding import rules_for_shape
        rules = rules_for_shape(shape.kind, multi_pod=multi_pod,
                                global_batch=shape.global_batch,
                                seq_len=shape.seq_len)
        rules.update(heads=None, kv_heads=None, ff=None,
                     act_heads=None, act_ff=None, vocab="model")
        step_kw["rules"] = rules

    jit_kw = {}
    if overrides and overrides.get("donate_cache") and shape.kind == "decode":
        # §Perf (serving): alias the KV cache in/out — removes the full
        # cache copy from every decode step (standard serving practice).
        jit_kw["donate_argnums"] = (1,)

    # 1) The deliverable compile: full depth, production scan/remat config.
    print(f"    [{arch}/{shape_name}/{mesh_kind}] main compile...", flush=True)
    main = _compile_cell(cfg, shape, mesh, multi_pod, step_kw, jit_kw)
    rec.update(ok=True, num_devices=mesh.devices.size, **main)

    # 2) Cost probes: XLA cost_analysis counts `while` bodies once, so the
    #    scanned-stack FLOPs are undercounted; two unrolled shallow compiles
    #    give the exact per-layer cost to extrapolate from.
    #    SSM-family train/prefill probes would unroll the inner chunk scans
    #    into enormous HLO (hour-long CPU compiles) — those cells report
    #    analytic model_flops instead (roofline marks them 'analytic').
    if (cfg.mamba or cfg.xlstm) and shape.kind != "decode":
        skip_probes = True
        rec["probe_note"] = "ssm inner scans: analytic flops (probe unroll too costly)"
    if not skip_probes:
        try:
            print(f"    [{arch}/{shape_name}/{mesh_kind}] probe compiles...",
                  flush=True)
            m1 = _compile_cell(_probe_cfg(cfg, 1), shape, mesh, multi_pod,
                               step_kw, jit_kw)
            m2 = _compile_cell(_probe_cfg(cfg, 2), shape, mesh, multi_pod,
                               step_kw, jit_kw)
            rec.update(_extrapolate(m1, m2, cfg.num_periods))
        except Exception as e:  # noqa: BLE001 — probes are best-effort:
            # the error (any compile failure) is RECORDED on the cell, not
            # swallowed — the roofline table shows the probe hole
            rec["probe_error"] = str(e)[:500]
            rec["probe_trace"] = traceback.format_exc()[-2000:]

    rec["model_flops"] = model_flops(cfg, shape)
    if rec.get("flops_est"):
        rec["useful_flops_ratio"] = rec["model_flops"] / (
            rec["flops_est"] * mesh.devices.size)
    return rec


def cell_path(arch, shape_name, mesh_kind, tag="") -> Path:
    sfx = f"--{tag}" if tag else ""
    return OUT_DIR / f"{arch}--{shape_name}--{mesh_kind}{sfx}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag (perf hillclimb)")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (e.g. remat=full, microbatch=4)")
    ap.add_argument("--no-probes", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    archs = list_archs() if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                path = cell_path(arch, shape_name, mesh_kind, args.tag)
                if path.exists() and not args.force:
                    print(f"cached  {path.name}")
                    n_ok += 1
                    continue
                t0 = time.time()
                try:
                    # probes (exact-FLOPs extrapolation) feed the single-pod
                    # roofline table; the multi-pod pass only proves sharding.
                    rec = run_cell(arch, shape_name, mesh_kind,
                                   overrides=overrides, tag=args.tag,
                                   skip_probes=(mesh_kind == "multi"
                                                or args.no_probes))
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                           "tag": args.tag, "ok": False, "error": str(e),
                           "trace": traceback.format_exc()[-2000:]}
                path.write_text(json.dumps(rec, indent=1))
                jax.clear_caches()  # keep one-process sweep memory bounded
                status = ("SKIP" if rec.get("skipped")
                          else "ok" if rec["ok"] else "FAIL")
                if rec.get("skipped"):
                    n_skip += 1
                elif rec["ok"]:
                    n_ok += 1
                else:
                    n_fail += 1
                print(f"{status:5s} {arch:18s} {shape_name:12s} {mesh_kind:6s} "
                      f"{time.time() - t0:7.1f}s "
                      f"flops={rec.get('flops', 0):.3g} "
                      f"coll={rec.get('collective_bytes', 0):.3g}B"
                      + (f"  ERR: {rec.get('error', '')[:120]}" if not rec["ok"] else ""))
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
