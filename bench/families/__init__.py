"""Model families, one module each: ``bench/families/<family>.py``.

A configuration names its family under the optional key
``"bench_family"`` (``dense`` where the key is absent); the harness loads
the module by that name (``harness.load_cell``), as it loads metric
readers and kernel costs (a test's data directory may hold its own
``families/<family>.py``, which comes first).  Nothing outside the module
names a family's own sizes, so a new architecture adds a module and data
files and edits none.  A family module defines:

``spec_from_config(cfg) -> spec``
    The benchmark's own description of the configuration file ``cfg``: a
    hashable value (it is a static argument of the reference's jitted
    programs) with at least ``name``, ``vocab`` (token ids are drawn
    below it) and ``decode_flops(rows, live) -> float``, the model FLOPs
    of one decode step over ``rows`` active rows whose live positions sum
    to ``live`` (read by ``metrics/decode_mfu.py``).

``make_weights(spec, seed) -> weights``
    Every weight from the seed, in one jitted call on the default device,
    in the dtypes they are served in.

``program_config(cfg, spec)``
    The program's ``ArchConfig`` for the configuration, checked against
    the spec's sizes; an architecture the family does not describe is an
    error.

``program_params(weights, spec)``
    The weights wrapped in the program's parameter tree, taking nothing
    the program made.

``score(weights, spec, seqs, want, *, group, length, rows, precision)``
    The plain reference, importing nothing of the program: f32 at
    ``highest`` matmul precision, or with ``precision="fp8"`` the control
    one precision below the configuration's.  ``seqs`` is a list of
    (tokens, prompt length, served count), ``want`` per sequence the token
    ids [served, k] whose logits are read; sequences run ``group`` at a
    time padded to ``length`` with ``rows`` >= every served count.
    Returns per sequence (argmax, max logit, logits of ``want``) at the
    positions that predict the served tokens (``check.py``).
"""
