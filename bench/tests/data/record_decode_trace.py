"""Record ``decode_named.xplane.pb.gz``: a small trace of the program's
paged decode on one TPU, its Pallas calls named by ``kernel_metadata``.

The model is the qwen2 layer of ``configs/tiny.json`` at 512 wide (2
layers, 4 heads of 128 over one KV head, d_ff 1024, vocab 1024), 4-bit
float weights and a kv4 cache, 4 slots of 128 in 16-token pages.  Four
requests are admitted and warmed; then three decode steps run under the
profiler, each inside a ``bench/step`` span as the load generator
writes them.

    python3 bench/tests/data/record_decode_trace.py <out.xplane.pb.gz>

Needs a TPU; exits with 3 without one.
"""

import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

WIDE = {"hidden_size": 512, "intermediate_size": 1024,
        "num_attention_heads": 4, "vocab_size": 1024}


def main(out: str) -> int:
    import jax
    import numpy as np

    from bench.families import dense
    from repro.serving.server import Server

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 3
    cfg = json.loads((Path(__file__).parent / "configs" / "tiny.json").read_text())
    cfg.update(WIDE)
    cfg["program"]["overrides"].update(d_model=512, n_heads=4, d_ff=1024,
                                       vocab_size=1024)
    spec = dense.spec_from_config(cfg)
    server = Server(dense.program_params(dense.make_weights(spec, 5), spec),
                    dense.program_config(cfg, spec), num_slots=4,
                    max_seq_len=128, page_size=16, paged=True, eos_id=None)
    rng = np.random.default_rng(0)
    for _ in range(4):
        server.submit(rng.integers(0, spec.vocab, 24, dtype=np.int32), 16)
    for _ in range(3):      # admission, then decode steps already compiled
        server.step()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                server.step()
        jax.profiler.stop_trace()
        (pb,) = Path(tmp).rglob("*.xplane.pb")
        with open(pb, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
