"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the timed run
served is drawn from the seed (``sample``), the longest always in it.  The
reference runs over each prompt followed by its served tokens, and each
served token is read by how far its reference logit lies below the
reference's best logit at that position: 0 where the served token is the
reference's greedy choice.  A cell compares the widest such gap, or, where
that does not separate sound runs from the control, the mean gap
(``bench/checks/<cell>.json``).
"""

from __future__ import annotations

import numpy as np

from bench import reference


def sample(logs, seed: int, check: dict) -> list:
    """``check["sample"]`` requests drawn from the seed among those the
    run finished, the longest first; or, where ``check["among"]`` is
    "served" (a backlog whose requests outlive the run), among every
    request that emitted two tokens or more, with the tokens served so
    far, the one that emitted most first."""
    n = check["sample"]
    if check.get("among", "finished") == "served":
        pool = sorted((g for g in logs if len(g.tokens) >= 2),
                      key=lambda g: (-len(g.tokens), -len(g.prompt)))
    else:
        pool = sorted((g for g in logs if len(g.tokens) >= g.max_new),
                      key=lambda g: -(len(g.prompt) + len(g.tokens)))
    if not pool:
        return []
    rng = np.random.default_rng([seed, 1])
    rest = list(rng.permutation(len(pool) - 1)[: n - 1] + 1)
    return [pool[0]] + [pool[i] for i in rest]


def decide(got: dict, limits: dict):
    """``correct`` and the numbers compared, each beside its limit: every
    reading named in `limits` at most its limit, and something compared."""
    compared = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    correct = got["tokens_compared"] > 0 and all(
        got[k] <= v for k, v in limits.items())
    return bool(correct), compared


def sequences(picked) -> list:
    """(prompt + served tokens but the last, prompt length, served count)."""
    return [(np.concatenate([g.prompt, np.asarray(g.tokens[:-1], np.int32)]),
             len(g.prompt), len(g.tokens)) for g in picked]


def gaps(weights, spec, picked, geometry: dict):
    """Per picked request, the reference gap of each served token."""
    if not picked:
        return []
    seqs = sequences(picked)
    want = [np.asarray(g.tokens, np.int32)[:, None] for g in picked]
    res = reference.score(weights, spec, seqs, want, **geometry)
    return [best - got[:, 0] for _, best, got in res]


def with_control(weights, spec, picked, geometry: dict):
    """The program's gaps and the control's on the same sequences.  The
    control is the reference computed one precision lower (fp8): at each
    position it chooses the token it ranks first, and the reference reads
    the gap of that choice."""
    seqs = sequences(picked)
    dummy = [np.zeros((len(g.tokens), 1), np.int32) for g in picked]
    low = reference.score(weights, spec, seqs, dummy, precision="fp8",
                          **geometry)
    want = [np.stack([np.asarray(g.tokens, np.int32), arg.astype(np.int32)], 1)
            for g, (arg, _, _) in zip(picked, low)]
    res = reference.score(weights, spec, seqs, want, **geometry)
    return ([best - got[:, 0] for _, best, got in res],
            [best - got[:, 1] for _, best, got in res])


def readings(gap_list) -> dict:
    """The numbers a cell may compare: the widest gap and the mean gap over
    every served token of the sample (inf where nothing was compared)."""
    g = np.concatenate(gap_list) if gap_list else np.zeros(0)
    return {"max_logit_gap": float(g.max()) if g.size else float("inf"),
            "mean_logit_gap": float(g.mean()) if g.size else float("inf"),
            "tokens_compared": int(g.size),
            "tokens_off_greedy": int(np.sum(g > 0))}
