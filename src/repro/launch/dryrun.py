import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a module entry point (``python -m repro.launch.dryrun``);
the XLA_FLAGS line above executes before ANY jax import so 512 host
devices exist when jax locks the device count.

For each cell it records:
  * compile success (the deliverable: the distribution config is coherent)
  * memory_analysis()  — per-device argument/temp/output bytes (fits proof)
  * cost_analysis()    — per-device HLO flops + bytes (roofline terms)
  * collective bytes   — parsed from the post-SPMD HLO text per collective
    kind (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute)
  * the three roofline terms in seconds + the dominant one.

Results append to a JSON file consumed by benchmarks/roofline.py and
EXPERIMENTS.md.
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro import configs
from repro.configs.shapes import SHAPES
from repro.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import LMHarness, SkipCell
from repro.roofline import roofline_terms

KINDS = {"train": "train", "prefill": "prefill", "decode": "decode"}


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             expert_parallel: bool = False, variant: str | None = None,
             verbose: bool = True) -> dict:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(len(mesh.devices.flatten()))
    cfg = None
    harness_kw = {}
    if variant:  # §Perf variants: TransformerConfig or harness overrides
        import dataclasses
        base = configs.get_arch(arch_id).CONFIG
        overrides = {}
        for item in variant.split(","):
            k, _, v = item.partition("=")
            val = (v == "" or v.lower() == "true") if v.lower() in (
                "", "true", "false") else (int(v) if v.isdigit() else v)
            if k in ("attn_tp", "micro_rows"):
                harness_kw[k] = val
            else:
                overrides[k] = val
        if overrides:
            cfg = dataclasses.replace(base, **overrides)
    harness = LMHarness(arch_id, cfg=cfg, expert_parallel=expert_parallel,
                        **harness_kw)
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "chips": n_chips,
        "kind": shape.kind,
        "expert_parallel": expert_parallel,
        "variant": variant,
        "status": "ok",
    }
    try:
        harness.check_cell(shape)
    except SkipCell as e:
        rec["status"] = "skip"
        rec["reason"] = str(e)
        return rec
    t0 = time.time()
    try:
        in_sh, out_sh, args = harness.shardings(shape, mesh, shape.kind)
        step = harness.step_fn(shape, mesh, shape.kind)
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        # loop-aware totals: cost_analysis() counts while bodies ONCE, so
        # scanned layers/microbatches undercount by 32..832x (DESIGN.md §7;
        # repro.hlo_analysis multiplies by known_trip_count).
        cost = analyze_hlo(hlo)
        coll = cost.as_dict()
        cfg = harness.cfg
        n_micro = (harness.n_microbatches(shape, mesh)
                   if shape.kind == "train" else 1)
        terms = roofline_terms(
            flops_per_device=cost.flops,
            bytes_per_device=cost.bytes_accessed,
            collective_bytes_per_device=coll["total_bytes"],
            cfg=cfg, shape=shape, n_chips=n_chips, n_micro=n_micro,
        )
        rec.update({
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "total_bytes": (ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes),
            },
            "cost": {
                "flops_per_device": cost.flops,
                "bytes_per_device": cost.bytes_accessed,
                # raw body-once numbers kept for reference
                "xla_flops_body_once": float(ca.get("flops", 0.0)),
                "xla_bytes_body_once": float(ca.get("bytes accessed", 0.0)),
            },
            "collectives": coll,
            "roofline": terms,
        })
        if verbose:
            mem_gb = rec["memory"]["total_bytes"] / 2**30
            print(f"  ok  mem={mem_gb:6.2f} GiB/dev  "
                  f"compute={terms['compute_s']:.3e}s "
                  f"memory={terms['memory_s']:.3e}s "
                  f"collective={terms['collective_s']:.3e}s "
                  f"dominant={terms['dominant']} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
                  flush=True)
    except SkipCell as e:
        rec["status"] = "skip"
        rec["reason"] = str(e)
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"  FAIL {type(e).__name__}: {str(e)[:200]}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="comma-separated TransformerConfig overrides for "
                         "§Perf variants, e.g. 'seq_parallel_attn=true'")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = configs.list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    records = []
    if os.path.exists(args.out):
        records = json.load(open(args.out))

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = (arch, shape, mp, args.expert_parallel, args.variant)
                print(f"[dryrun] {arch} x {shape} x "
                      f"{'multi' if mp else 'single'}"
                      f"{' EP' if args.expert_parallel else ''}"
                      f"{' [' + args.variant + ']' if args.variant else ''}",
                      flush=True)
                rec = run_cell(arch, shape, multi_pod=mp,
                               expert_parallel=args.expert_parallel,
                               variant=args.variant)
                records = [r for r in records
                           if (r["arch"], r["shape"],
                               r["mesh"].startswith("multi"),
                               r.get("expert_parallel", False),
                               r.get("variant")) != key]
                records.append(rec)
                json.dump(records, open(args.out, "w"), indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    n_fail = sum(r["status"] == "fail" for r in records)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} fail "
          f"-> {args.out}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
