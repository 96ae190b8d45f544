"""Assemble EXPERIMENTS.md §Dry-run and §Roofline tables from the dry-run
manifests (baseline = artifacts/dryrun_baseline, optimized =
artifacts/dryrun).  §Perf prose is maintained by hand in EXPERIMENTS.md;
this script prints the per-cell before/after used there.

    PYTHONPATH=src python -m benchmarks.make_experiments > artifacts/experiments_tables.md
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.configs import SHAPES
from repro.configs.registry import get_arch
from repro.launch.mesh import TARGET_KIND, device_peaks

ROOT = Path(__file__).resolve().parents[1] / "artifacts"


def load(d, mesh):
    out = {}
    for p in sorted((ROOT / d).glob(f"*__{mesh}.json")):
        r = json.loads(p.read_text())
        out[(r["arch"], r["shape"])] = r
    return out


def fmt_cell(r, floor_fn):
    if r["status"] != "ok":
        return None
    rl = r["roofline"]
    return rl


def dryrun_section():
    print("## §Dry-run\n")
    for mesh, title in (("pod16x16", "single pod (16x16 = 256 chips)"),
                        ("pod2x16x16", "multi-pod (2x16x16 = 512 chips)")):
        recs = load("dryrun", mesh)
        base = load("dryrun_baseline", mesh)
        use = recs if recs else base
        ok = sum(1 for r in use.values() if r["status"] == "ok")
        sk = sum(1 for r in use.values() if r["status"] == "skipped")
        print(f"### {title}: {ok} compiled, {sk} documented skips\n")
        print("| arch | shape | kind | compile s | peak GB/dev | args GB/dev |"
              " HLO GFLOP/dev | coll GB/dev |")
        print("|---|---|---|---|---|---|---|---|")
        for (a, s), r in sorted(use.items()):
            if r["status"] != "ok":
                print(f"| {a} | {s} | skip | - | - | - | - | {r['reason'][:45]} |")
                continue
            m = r["memory"]
            print(f"| {a} | {s} | {r['kind']} | {r['compile_s']:.0f} | "
                  f"{m['peak_estimate']/1e9:.2f} | {m['argument_bytes']/1e9:.2f} | "
                  f"{r['hlo_cost']['flops_per_device']/1e9:.1f} | "
                  f"{r['hlo_cost']['collective_bytes_per_device']/1e9:.3f} |")
        print()


def roofline_section():
    from benchmarks.roofline import analytic_memory_floor

    print("## §Roofline (single pod, optimized build)\n")
    recs = load("dryrun", "pod16x16")
    print("| arch | shape | compute ms | memory ms | collective ms | floor ms"
          " | bottleneck | MODEL/HLO FLOPs | roofline-MFU |")
    print("|---|---|---|---|---|---|---|---|---|")
    for (a, s), r in sorted(recs.items()):
        if r["status"] != "ok":
            continue
        cfg = get_arch(a)
        shape = SHAPES[s]
        rl = r["roofline"]
        floor = analytic_memory_floor(cfg, shape, r["kind"], r["devices"]) / device_peaks(TARGET_KIND)["hbm_bw"] * 1e3
        print(f"| {a} | {s} | {rl['compute_ms']:.2f} | {rl['memory_ms']:.2f} |"
              f" {rl['collective_ms']:.2f} | {floor:.2f} | {rl['bottleneck']} |"
              f" {rl['useful_flops_ratio']:.2f} | {rl['roofline_mfu']:.3f} |")
    print()


def perf_deltas():
    print("## §Perf raw before/after (baseline -> optimized)\n")
    print("| arch | shape | C ms b->o | M ms b->o | N ms b->o | peak GB b->o |")
    print("|---|---|---|---|---|---|")
    base = load("dryrun_baseline", "pod16x16")
    opt = load("dryrun", "pod16x16")
    for key in sorted(set(base) & set(opt)):
        b, o = base[key], opt[key]
        if b["status"] != "ok" or o["status"] != "ok":
            continue
        rb, ro = b["roofline"], o["roofline"]
        print(f"| {key[0]} | {key[1]} | "
              f"{rb['compute_ms']:.1f}->{ro['compute_ms']:.1f} | "
              f"{rb['memory_ms']:.1f}->{ro['memory_ms']:.1f} | "
              f"{rb['collective_ms']:.1f}->{ro['collective_ms']:.1f} | "
              f"{b['memory']['peak_estimate']/1e9:.2f}->"
              f"{o['memory']['peak_estimate']/1e9:.2f} |")
    print()


if __name__ == "__main__":
    dryrun_section()
    roofline_section()
    perf_deltas()
