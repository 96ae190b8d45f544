"""Find the knee of an open-loop cell: offer its mix at several rates and
see at which the backlog starts to grow.  One process, one set of weights;
at each rate the pool starts from its steady population at that rate
(``harness.in_flight``), as a run of the cell does.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 30

Prints one JSON line per rate: requests due in the window, how many had
no first token when it closed, time to first token of the first and last
quarter of them (it climbs through the window once the queue grows), and
the inter-token gap and token rate.  The knee is the highest rate at which
every request was served and the last quarter waited no longer than the
first; a cell offers 0.8 of it (bench/traffic/<mix>.json).
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse
    import copy
    import gc
    import json

    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30)
    p.add_argument("--seed", type=int, default=1)
    a = p.parse_args()

    from bench import harness, loop, model, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, a.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    harness._enable_compile_cache()
    from repro.serving.server import Server

    spec = model.spec_from_config(cell.config)
    cfg = model.program_config(cell.config, spec)
    weights = model.make_weights(spec, a.seed)
    for rate in (float(r) for r in a.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrival"]["rate_per_s"] = rate
        reqs = traffic.schedule(mix, a.seed, a.seconds, spec.vocab)
        pool = mix["pool"]
        server = Server(model.program_params(weights, spec), cfg,
                        num_slots=pool["num_slots"],
                        max_seq_len=pool["max_seq_len"],
                        page_size=pool["page_size"], paged=True, eos_id=None)
        t = time.perf_counter()
        warmed = harness._warm_up(server, reqs, spec.vocab)
        seeded = harness.in_flight(mix, server, a.seed, spec.vocab,
                                   min(len(r.prompt) for r in reqs))
        harness._warm_up(server, seeded, spec.vocab, warmed)
        warm = time.perf_counter() - t
        t_open = float(mix["warmup_s"])
        logs, _, ends = loop.drive(server, reqs, t_open=t_open,
                                   t_close=t_open + a.seconds, drain_s=0,
                                   drain=False, seeded=seeded)
        st = loop.window_stats(logs, t_open, t_open + a.seconds, False, ends)
        inside = sorted((g for g in logs.values()
                         if t_open <= g.due < t_open + a.seconds),
                        key=lambda g: g.due)
        q = max(1, len(inside) // 4)

        def ttft(gs):
            v = [g.times[0] - g.due for g in gs if g.times]
            return float(np.median(v)) if v else None

        print(json.dumps({
            "rate_per_s": rate, "in_flight_at_start": len(seeded),
            "due": st["attempted"],
            "no_first_token": st["failed"], "warm_up_s": warm,
            "ttft_p50_s": float(np.median(st["ttft"])) if st["ttft"] else None,
            "ttft_p95_s": float(np.percentile(st["ttft"], 95)) if st["ttft"] else None,
            "ttft_first_quarter_s": ttft(inside[:q]),
            "ttft_last_quarter_s": ttft(inside[-q:]),
            "itl_p50_ms": 1e3 * float(np.median(st["gaps"])) if st["gaps"] else None,
            "itl_p95_ms": 1e3 * float(np.percentile(st["gaps"], 95)) if st["gaps"] else None,
            "tokens_per_s": st["tokens"] / a.seconds,
            "running_at_close": len(server.scheduler.running),
            "queued_at_close": server.scheduler.n_queued}), flush=True)
        del server
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
