"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and report memory / cost / collective analysis.

MUST set the placeholder-device flag before any other import touches jax —
jax locks the device count on first backend initialization.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax           # noqa: E402

from repro import sharding                      # noqa: E402
from repro.config import SHAPES, load_config, shape_kind  # noqa: E402
from repro.configs import assigned_archs        # noqa: E402
from repro.launch import mesh as mesh_lib       # noqa: E402
from repro.launch import specs as specs_lib     # noqa: E402
from repro.serve import engine as engine_lib    # noqa: E402
from repro.train import train_loop              # noqa: E402


def _rules_kind(shape: str) -> str:
    return "long" if shape == "long_500k" else shape_kind(shape)


def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               do_compile: bool = True, overrides=None) -> Dict[str, Any]:
    """Lower (and compile) one cell; returns the §Dry-run/§Roofline record."""
    cfg = load_config(arch, shape, overrides=overrides)
    runnable, reason = specs_lib.cell_is_runnable(cfg)
    if not runnable:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    kind = shape_kind(shape)
    rules = mesh_lib.make_rules(cfg, mesh, _rules_kind(shape))
    if cfg.quant.container_dtype == "int8_packed" and kind == "train":
        rules["#packed_slice_specs"] = mesh_lib.packed_slice_specs(
            specs_lib.param_specs(cfg), cfg, mesh)
    t0 = time.time()

    with sharding.use_rules(mesh, rules):
        if kind == "train":
            state_sh = mesh_lib.state_shardings(
                specs_lib.state_specs(cfg), cfg, mesh)
            batch_sh = mesh_lib.batch_shardings(
                specs_lib.batch_specs(cfg), mesh)
            fn = train_loop.make_train_step(
                cfg, qparam_shardings=state_sh["params"])
            jfn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None))
            lowered = jfn.lower(specs_lib.state_specs(cfg),
                                specs_lib.batch_specs(cfg))
        elif kind == "prefill":
            sp = specs_lib.prefill_specs(cfg)
            qsh = mesh_lib.param_shardings(sp["qparams"], cfg, mesh)
            dsh = mesh_lib.batch_shardings(
                {k: v for k, v in sp.items() if k != "qparams"}, mesh)
            m = cfg.model
            if m.is_encoder:
                from repro.models import transformer

                def fn(qparams, embeds):
                    return transformer.forward(qparams, m, embeds=embeds)
                jfn = jax.jit(fn, in_shardings=(qsh, dsh["embeds"]))
                lowered = jfn.lower(sp["qparams"], sp["embeds"])
            else:
                pf = engine_lib.make_prefill(cfg)
                args = [sp["qparams"], sp["tokens"]]
                in_sh = [qsh, dsh["tokens"]]
                if "memory" in sp:
                    args.append(sp["memory"])
                    in_sh.append(dsh["memory"])
                jfn = jax.jit(pf, in_shardings=tuple(in_sh))
                lowered = jfn.lower(*args)
        else:  # decode / long-context decode
            sp = specs_lib.decode_specs(cfg)
            qsh = mesh_lib.param_shardings(sp["qparams"], cfg, mesh)
            csh = mesh_lib.cache_shardings(sp["caches"], cfg, mesh,
                                           _rules_kind(shape))
            tsh = mesh_lib.batch_shardings(
                {"token": sp["token"]}, mesh,
                kind)["token"] if shape != "long_500k" else \
                mesh_lib.replicated(mesh)
            fn = engine_lib.make_decode(cfg)
            jfn = jax.jit(fn, in_shardings=(
                qsh, tsh, csh, mesh_lib.replicated(mesh)),
                out_shardings=(None, csh))
            lowered = jfn.lower(sp["qparams"], sp["token"], sp["caches"],
                                sp["t"])

    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "status": "lowered", "lower_s": round(time.time() - t0, 1),
        "devices": mesh.devices.size, "kind": kind,
    }
    if do_compile:
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec["status"] = "compiled"
        mem = compiled.memory_analysis()
        if mem is not None:
            rec["memory"] = {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or
                                  getattr(mem, "temp_size_in_bytes", 0)),
            }
        from repro.roofline import hlo_costs
        c = compiled.cost_analysis()
        if c:
            # NB: XLA counts while bodies once — kept for reference only;
            # the roofline uses the trip-count-aware walker below.
            rec["xla_cost_analysis"] = {
                k: float(v) for k, v in c.items()
                if isinstance(v, (int, float)) and
                k in ("flops", "bytes accessed", "transcendentals")}
        walked = hlo_costs.module_costs(compiled.as_text())
        rec["cost"] = {"flops": walked["flops"],
                       "bytes accessed": walked["bytes"]}
        rec["collectives"] = walked["collectives"]
        rec["dynamic_loops"] = walked["dynamic_loops"]
    return rec


def run_cells(archs, shapes, *, multi_pod: bool, do_compile: bool,
              out_dir: Optional[str], overrides=None):
    results = []
    for arch in archs:
        for shape in shapes:
            tag = f"{arch} × {shape} × {'2pod' if multi_pod else '1pod'}"
            try:
                rec = lower_cell(arch, shape, multi_pod=multi_pod,
                                 do_compile=do_compile, overrides=overrides)
                status = rec["status"]
                extra = rec.get("reason", "")
                if "cost" in rec:
                    extra = (f"flops={rec['cost'].get('flops', 0):.3e} "
                             f"compile={rec.get('compile_s')}s")
                print(f"[dryrun] {tag}: {status} {extra}", flush=True)
            except Exception as e:  # a failed cell is a bug — record & move on
                rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                       "status": "FAILED", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"[dryrun] {tag}: FAILED {e}", flush=True)
            results.append(rec)
            if out_dir:
                os.makedirs(out_dir, exist_ok=True)
                name = (f"{arch}_{shape}_{'2pod' if multi_pod else '1pod'}"
                        .replace("/", "_").replace(".", "_"))
                with open(os.path.join(out_dir, name + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="dotted config overrides, e.g. quant.mode=off")
    args = ap.parse_args(argv)

    archs = assigned_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    all_results = []
    for mp in meshes:
        all_results += run_cells(archs, shapes, multi_pod=mp,
                                 do_compile=not args.no_compile,
                                 out_dir=args.out, overrides=args.override)
    failed = [r for r in all_results if r["status"] == "FAILED"]
    print(f"\n[dryrun] {len(all_results)} cells: "
          f"{sum(r['status'] == 'compiled' for r in all_results)} compiled, "
          f"{sum(r['status'] == 'skipped' for r in all_results)} skipped, "
          f"{len(failed)} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
