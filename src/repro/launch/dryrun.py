import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh over 512 placeholder host devices, lowers the cell's step
function against abstract ShapeDtypeStruct inputs (no allocation), compiles,
and extracts memory_analysis / cost_analysis / the collective schedule for
the roofline (§Roofline in EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str | None = None,
             attention_impl: str | None = None, overrides: dict | None = None) -> dict:
    import dataclasses

    import jax

    from repro.configs import registry
    from repro.configs.base import SHAPES, shape_applies
    from repro.launch import roofline, specs
    from repro.launch.mesh import make_production_mesh
    from repro.optim.optimizers import adamw
    from repro.train import train_state as ts

    cfg = registry.get(arch)
    if attention_impl:
        cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applies(cfg, shape)
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}.json"
            with open(os.path.join(out_dir, tag), "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    opt = adamw()
    t0 = time.time()
    try:
        with mesh:
            if shape.kind == "train":
                step_fn = ts.make_train_step(
                    cfg, opt, lambda s: 1e-4
                )
                args = specs.input_specs(cfg, mesh, shape, opt)
                lowered = jax.jit(step_fn).lower(*args)
            elif shape.kind == "prefill":
                step_fn = ts.make_prefill_step(cfg)
                params, batch, caches = specs.input_specs(cfg, mesh, shape, opt)
                lowered = jax.jit(step_fn).lower(params, batch, caches)
            else:  # decode
                step_fn = ts.make_serve_step(cfg)
                params, caches, batch = specs.input_specs(cfg, mesh, shape, opt)
                lowered = jax.jit(step_fn).lower(params, caches, batch)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        from repro.launch import hlo_analysis

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        totals = hlo_analysis.analyze(hlo_text)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=roofline.memory_summary(mem),
            # raw XLA numbers (while bodies counted once — kept for reference)
            xla_cost={k: cost.get(k) for k in ("flops", "bytes accessed")},
            # trip-count-aware per-device totals (see hlo_analysis.py)
            hlo={
                "flops": totals["flops"],
                "bytes": totals["bytes"],
                "collective_bytes": totals["collective_bytes"],
                "collective_counts": totals["collective_counts"],
                "collective_total_bytes": totals["collective_total_bytes"],
                "collective_shapes": dict(sorted(
                    totals["collective_shapes"].items(), key=lambda kv: -kv[1])[:12]),
                "while_trips": totals["while_trips"],
            },
        )
        rec["roofline"] = roofline.roofline_terms_from_hlo(
            cfg, shape, totals, multi_pod=multi_pod
        )
    except Exception as e:  # noqa: BLE001 — report per-cell failures
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}.json"
        with open(os.path.join(out_dir, tag), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--attention-impl", default=None)
    args = ap.parse_args()

    from repro.configs import registry
    from repro.configs.base import SHAPES

    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for a in registry.ARCHS:
            for s in SHAPES:
                cells.append((a, s, False))
                cells.append((a, s, True))
    else:
        cells.append((args.arch, args.shape, args.multi_pod))

    failed = 0
    for arch, shape, mp in cells:
        rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                       attention_impl=args.attention_impl)
        status = rec["status"]
        extra = ""
        if status == "ok":
            rl = rec["roofline"]
            extra = (f" compile={rec['compile_s']}s bound={rl['bound']}"
                     f" frac={rl['roofline_fraction']:.3f}"
                     f" useful={rl['useful_flops_ratio']:.2f}")
        elif status == "error":
            extra = " " + rec["error"][:200]
            failed += 1
        print(f"[{status:7s}] {arch} x {shape} ({rec['mesh']}){extra}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
