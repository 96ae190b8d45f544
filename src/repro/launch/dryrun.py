import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: prove every (architecture x input shape x mesh) cell
lowers AND compiles on the production mesh, and extract the roofline
inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]

Per cell this records into artifacts/dryrun/<arch>__<shape>__<mesh>.json:
  * memory_analysis (bytes/device: args, temps, output) — proves it fits
  * xla cost_analysis (flops / bytes, NOT trip-count-corrected)
  * hierarchical HLO cost (utils/hlo.py): flops, HBM bytes, collective
    bytes PER DEVICE, while-bodies multiplied by known_trip_count
  * the roofline terms vs TPU v5e peaks (see benchmarks/roofline.py)

The 512-device XLA flag above must precede every other import — jax locks
the device count at first init.  Never set it in conftest/pyproject.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, shape_applicable
from repro.configs.registry import ASSIGNED, get_arch
from repro.launch import mesh as mesh_mod
from repro.launch.specs import Skip, build_cell
from repro.utils.hlo import compiled_cost

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape: str, *, multi_pod: bool, save: bool = True,
             hlo_dir: str | None = None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "devices": mesh.size}
    try:
        cell = build_cell(arch, shape, mesh)
    except Skip as e:
        record["status"] = "skipped"
        record["reason"] = str(e)
        print(f"[skip] {arch} x {shape} x {mesh_name}: {e}")
        if save:
            _save(record)
        return record

    with mesh:
        jitted = jax.jit(
            cell["fn"],
            in_shardings=cell["in_shardings"],
            out_shardings=cell["out_shardings"],
            donate_argnums=cell["donate_argnums"],
        )
        lowered = jitted.lower(*cell["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    cost = compiled_cost(compiled)
    if hlo_dir:
        Path(hlo_dir).mkdir(parents=True, exist_ok=True)
        (Path(hlo_dir) / f"{arch}__{shape}__{mesh_name}.hlo").write_text(
            compiled.as_text())

    cfg = cell["cfg"]
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = cell["meta"]["tokens"]
    kind = cell["meta"]["kind"]
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens  # global

    record.update(
        status="ok",
        kind=kind,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        tokens=tokens,
        n_params=n_params,
        n_active_params=n_active,
        model_flops_global=model_flops,
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            peak_estimate=ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes,
        ),
        xla_cost=dict(
            flops=cost["xla_flops"],
            bytes_accessed=cost["xla_bytes_accessed"],
        ),
        hlo_cost=dict(
            flops_per_device=cost["flops"],
            hbm_bytes_per_device=cost["hbm_bytes"],
            collective_bytes_per_device=cost["collective_bytes"],
        ),
    )
    record.update(_roofline(record, mesh.size))
    hbm_gb = record["memory"]["peak_estimate"] / 1e9
    print(
        f"[ok] {arch} x {shape} x {mesh_name}: "
        f"compile {t_compile:.0f}s, peak {hbm_gb:.2f} GB/dev, "
        f"terms(ms) C={record['roofline']['compute_ms']:.2f} "
        f"M={record['roofline']['memory_ms']:.2f} "
        f"N={record['roofline']['collective_ms']:.2f} "
        f"-> {record['roofline']['bottleneck']}"
    )
    if save:
        _save(record)
    return record


def _roofline(record: dict, n_chips: int) -> dict:
    c = record["hlo_cost"]
    peaks = mesh_mod.device_peaks(mesh_mod.TARGET_KIND)
    compute_s = c["flops_per_device"] / peaks["flops_bf16"]
    memory_s = c["hbm_bytes_per_device"] / peaks["hbm_bw"]
    collective_s = c["collective_bytes_per_device"] / peaks["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = record["model_flops_global"] / max(
        c["flops_per_device"] * n_chips, 1.0
    )
    step_s = max(terms.values())
    mfu = record["model_flops_global"] / (
        n_chips * peaks["flops_bf16"] * step_s
    ) if step_s > 0 else 0.0
    return {
        "roofline": {
            "compute_ms": compute_s * 1e3,
            "memory_ms": memory_s * 1e3,
            "collective_ms": collective_s * 1e3,
            "bottleneck": bottleneck,
            "useful_flops_ratio": useful,
            "roofline_mfu": mfu,
        }
    }


def _save(record: dict):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    with open(ARTIFACTS / name, "w") as f:
        json.dump(record, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--hlo-dir", default=None, help="also dump HLO text")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
                out = ARTIFACTS / f"{arch}__{shape}__{mesh_name}.json"
                if args.skip_existing and out.exists():
                    print(f"[cached] {arch} x {shape} x {mesh_name}")
                    continue
                try:
                    run_cell(arch, shape, multi_pod=multi_pod,
                             hlo_dir=args.hlo_dir)
                except Exception:
                    failures.append((arch, shape, mesh_name))
                    print(f"[FAIL] {arch} x {shape} x {mesh_name}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nDry-run complete: all cells lowered + compiled.")


if __name__ == "__main__":
    main()
