import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512"]))
# ^ MUST run before ANY jax import: jax locks the device count on first
# init. 512 placeholder host devices back the production mesh; flags the
# caller already set are kept.

"""Multi-pod dry-run: lower + compile every (architecture × shape) cell on
the production meshes and extract memory / cost / collective analyses.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--multi-pod | --single-pod | --both] [--out results/dryrun]

Each cell writes an incremental JSON (results survive interruptions; rerun
skips completed cells unless --force). Failures here are bugs in the
framework's sharding config — fix, rerun, iterate.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import ARCHS, SHAPES, cells_for, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell
from repro.sharding import DistContext

# TPU v5e constants (target hardware; see ROOFLINE ANALYSIS spec)
PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link (per-chip effective, one direction)

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=?")


def _parse_shape_bytes(shape_str: str) -> int:
    """'bf16[8,128,2048]{...}' -> byte count. Tuples handled by caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str.strip())
    if not m:
        return 0
    dt, dims = m.groups()
    sizes = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
    b = sizes.get(dt)
    if b is None:
        return 0
    if not dims:
        return b
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in (post-SPMD,
    per-device) HLO. Returns {kind: bytes, 'total': bytes, 'count': n}."""
    out: dict = {}
    count = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(
            r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        shape_part, kind = m.groups()
        if shape_part.startswith("("):
            nbytes = sum(_parse_shape_bytes(s)
                         for s in re.findall(r"[a-z0-9]+\[[0-9,]*\]",
                                             shape_part))
        else:
            nbytes = _parse_shape_bytes(shape_part)
        out[kind] = out.get(kind, 0) + nbytes
        count += 1
    out["total"] = sum(v for k, v in out.items() if k != "count")
    out["count"] = count
    return out


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode D = new tokens only."""
    n = cfg.param_count(active_only=True)
    if shape.step == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.step == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _compile_cell(arch, shape, multi_pod, overrides, costing_periods=None):
    """-> (compiled, mesh, cell) for one program variant."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(overrides or {})
    flags = frozenset(overrides.pop("dist_flags", ()))
    dist = DistContext(mesh, flags=flags)
    cell = make_cell(arch, shape, dist, overrides=overrides,
                     costing_periods=costing_periods)
    with mesh:
        jitted = jax.jit(cell.fn,
                         in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return compiled, mesh, cell


def _costs_of(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, force: bool = False,
             overrides: dict | None = None, tag: str = "",
             costing: bool | None = None) -> dict:
    """Full rolled compile (lowering proof + memory analysis) plus — on the
    single-pod mesh — two shallow *unrolled* costing compiles at L∈{2,4}
    periods, linearly extrapolated to full depth (exact: the scan body is
    identical per period). Train totals = µ × fwd/bwd(microbatch) + analytic
    optimizer apply. This sidesteps XLA cost_analysis counting while-loop
    bodies once."""
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}" + (f"__{tag}" if tag else "")
    out_path = out_dir / f"{name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    if costing is None:
        costing = not multi_pod  # roofline table is single-pod only

    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                 "step": shape.step, "tag": tag,
                 "seq_len": shape.seq_len, "global_batch": shape.global_batch}
    t0 = time.time()
    try:
        compiled, mesh, cell = _compile_cell(arch, shape, multi_pod,
                                             overrides)
        t_full = time.time()
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
        per_dev_bytes = sum(v for v in [
            rec["memory"]["argument_bytes"], rec["memory"]["temp_bytes"],
            rec["memory"]["output_bytes"]] if v) - (
                rec["memory"]["alias_bytes"] or 0)
        rec["memory"]["per_device_total_bytes"] = per_dev_bytes
        rec["memory"]["fits_16gb"] = bool(per_dev_bytes < 16e9)
        rec["full_compile_hlo_bytes"] = len(compiled.as_text())
        rec["timings"] = {"full_compile_s": t_full - t0}
        rec["ok"] = True

        if costing:
            knobs = {}
            mb = 1
            if shape.step == "train":
                from repro.launch.specs import resolve_knobs
                from repro.sharding import DistContext as _DC
                knobs = resolve_knobs(
                    cfg, _DC(make_production_mesh(multi_pod=multi_pod)),
                    shape.global_batch,
                    {k: v for k, v in (overrides or {}).items()
                     if k != "dist_flags"})
                mb = max(1, knobs.get("microbatch") or 1)
            n_p = cfg.n_periods
            l1, l2 = (2, 4) if n_p >= 4 else (1, max(2, n_p))
            c1, _, _ = _compile_cell(arch, shape, multi_pod, overrides,
                                     costing_periods=l1)
            k1 = _costs_of(c1)
            if l2 != l1 and n_p != l1:
                c2, _, _ = _compile_cell(arch, shape, multi_pod, overrides,
                                         costing_periods=l2)
                k2 = _costs_of(c2)
            else:
                k2, l2 = k1, l1
            t_cost = time.time()

            def extrap(a, b):
                if l2 == l1:
                    return b
                return b + (b - a) / (l2 - l1) * (n_p - l2)

            flops = extrap(k1["flops"], k2["flops"]) * mb
            byts = extrap(k1["bytes"], k2["bytes"]) * mb
            coll_kinds = set(k1["coll"]) | set(k2["coll"])
            coll = {kk: extrap(k1["coll"].get(kk, 0), k2["coll"].get(kk, 0))
                    * mb for kk in coll_kinds}
            if shape.step == "train":
                from repro.launch.specs import (optimizer_analytic_costs,
                                                optimizer_for)
                oc = optimizer_analytic_costs(
                    cfg, optimizer_for(cfg), knobs.get("accum_dtype",
                                                       "float32"), mesh.size)
                flops += oc["flops_per_device"]
                byts += oc["bytes_per_device"]
            rec["cost"] = {"flops_per_device": flops,
                           "bytes_accessed_per_device": byts,
                           "costing_periods": [l1, l2],
                           "microbatch": mb}
            rec["collectives"] = {k: v for k, v in coll.items()}
            rec["timings"]["costing_s"] = t_cost - t_full

            n_dev = mesh.size
            mf = model_flops(cfg, shape)
            comp_t = flops / PEAK_FLOPS
            mem_t = byts / HBM_BW
            # floor: every resident byte (params/opt/caches/IO) streamed once
            floor_bytes = (rec["memory"]["argument_bytes"] or 0) + \
                          (rec["memory"]["output_bytes"] or 0) - \
                          (rec["memory"]["alias_bytes"] or 0)
            mem_floor_t = floor_bytes / HBM_BW
            coll_t = coll.get("total", 0.0) / ICI_BW
            dominant = max((("compute", comp_t), ("memory", mem_t),
                            ("collective", coll_t)), key=lambda kv: kv[1])[0]
            bound = max(comp_t, mem_t, coll_t)
            rec["roofline"] = {
                "compute_s": comp_t,
                "memory_s": mem_t,
                "memory_floor_s": mem_floor_t,
                "collective_s": coll_t,
                "dominant": dominant,
                "model_flops_total": mf,
                "model_flops_per_device": mf / n_dev,
                "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
                "step_time_bound_s": bound,
                "mfu_bound": (mf / n_dev / PEAK_FLOPS) / bound
                             if bound > 0 else 0.0,
            }
    except Exception as exc:  # noqa: BLE001 — record the failure, keep going
        rec["ok"] = False
        rec["error"] = repr(exc)
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["elapsed_s"] = time.time() - t0
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    status = "OK " if rec.get("ok") else "FAIL"
    r = rec.get("roofline", {})
    print(f"[{status}] {name}  "
          f"compute={r.get('compute_s', 0):.4f}s mem={r.get('memory_s', 0):.4f}s "
          f"coll={r.get('collective_s', 0):.4f}s dom={r.get('dominant', '-')} "
          f"mfu_bound={r.get('mfu_bound', 0):.3f} "
          f"({rec['elapsed_s']:.0f}s)", flush=True)
    if not rec.get("ok"):
        print(rec.get("error"), flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    meshes = []
    if args.both or (not args.multi_pod and not args.single_pod):
        meshes = [False, True]
    else:
        if args.single_pod:
            meshes.append(False)
        if args.multi_pod:
            meshes.append(True)

    archs = [args.arch] if args.arch else list(ARCHS)
    out_dir = Path(args.out)
    results = []
    for arch in archs:
        shapes = cells_for(arch)
        if args.shape:
            shapes = [s for s in shapes if s.name == args.shape]
        for shape in shapes:
            for mp in meshes:
                results.append(run_cell(arch, shape.name, mp, out_dir,
                                        force=args.force))
    ok = sum(r.get("ok", False) for r in results)
    print(f"\n{ok}/{len(results)} cells compiled successfully")
    if ok < len(results):
        sys.exit(1)


if __name__ == "__main__":
    main()
