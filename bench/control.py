"""Readings from which a cell's correctness limit is set.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2 --seconds 15

For each seed, one process serves the cell's traffic at its own load and
sizes for a short window (as ``run.py`` does, without metrics), samples
the served requests as ``run.py`` does and prints the readings of the
program: the widest and the mean gap of a served token below the
reference's best.  On the control seeds it also reads the control: the
reference computed in fp8, one precision below the configuration's bf16,
choosing the token at each position of the same sequences.  Each side's
readings go through the cell's own decision (``check.decide`` against
``bench/checks/<cell>.json``), and the line says whether each comes out
correct: the program has to, the control must not.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse
    import json
    import time

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=15)
    a = p.parse_args()

    from bench import check, harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, a.workload)
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        try:
            ctx, logs, weights = harness.serve(cell, seed, a.seconds, False)
        except harness.NoChip as e:
            print(f"no measurement: {e}", file=sys.stderr)
            return 3
        picked = check.sample(logs, seed, cell.check)
        geo = cell.check["geometry"]
        row = {"seed": seed, "sampled": len(picked),
               "served_tokens": sum(len(g.tokens) for g in picked)}
        if seed in ctrl:
            prog, low = check.with_control(weights, ctx.spec, picked, geo)
            row["control"] = check.readings(low)
            row["control_correct"] = check.decide(
                row["control"], cell.check["limits"])[0]
        else:
            prog = check.gaps(weights, ctx.spec, picked, geo)
        row["program"] = check.readings(prog)
        row["program_correct"] = check.decide(
            row["program"], cell.check["limits"])[0]
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
