"""Benchmark suite entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table1,...]

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable logs on
stderr) and writes machine-readable results under artifacts/bench/.
"""

from __future__ import annotations

import argparse
import sys
import time


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig3dt,fig3bs,fig4,table1,appb,"
                         "kernel,roofline,serve,figmix,plan,ledger")
    ap.add_argument("--all", action="store_true",
                    help="run every suite (the default when --only is unset; "
                         "spelled out for scripts/CI)")
    args = ap.parse_args()
    if args.all and args.only:
        ap.error("--all and --only are mutually exclusive")
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (appb_centering, fig2_bitlevel, fig3_blocksize,
                            fig3_datatypes, fig4_proxy, fig_mixed_frontier,
                            kernel_bench, ledger, roofline, serve_bench,
                            table1_gptq)

    suites = {
        "fig2": fig2_bitlevel.run,
        "fig3dt": fig3_datatypes.run,
        "fig3bs": fig3_blocksize.run,
        "fig4": fig4_proxy.run,
        "table1": table1_gptq.run,
        "appb": appb_centering.run,
        "kernel": kernel_bench.run,
        "roofline": roofline.run,
        "serve": serve_bench.run,
        "figmix": fig_mixed_frontier.run,
        "plan": fig_mixed_frontier.run_plan,
        "ledger": ledger.run,
    }
    wanted = ([n for n in args.only.split(",") if n] if args.only
              else list(suites))
    unknown = sorted(set(wanted) - set(suites))
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; valid: {sorted(suites)}")
    if not wanted:
        ap.error("--only names no suites")
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.time()
        log(f"\n==== {name} ====")
        rows, _ = suites[name](log=log)
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
        log(f"[{name} done in {time.time()-t0:.0f}s]")


if __name__ == "__main__":
    main()
