"""Architectures, one module each, named by the ``architecture`` string of
the configurations that use it (``bench/configs/<config>.json``) and found
by :func:`bench.spec.arch`.  A module provides:

- ``model_config(cfg)`` and ``to_program(w, cfg)``: the program's model
  configuration and its parameter tree from the benchmark's weights.  They
  import the program inside the function; only ``bench/system.py`` and
  ``bench/fit.py`` call them.
- ``LAYER_LEAVES``, ``TOP_LEAVES``, ``NORMS``, ``layer_shapes(cfg)`` and
  ``top_shapes(cfg)``: the tensors :mod:`bench.weights` draws from the
  seed.  A leaf's position in its tuple is part of its key, so a name once
  listed keeps its place.
- ``hidden(seed, cfg, seqs, rows, quant=None)``: the float32 reference's
  final normalised hidden states at ``rows`` and the head, importing
  nothing of the program (:func:`bench.check.compare` reads them).
- ``decode_step_flops(cfg, contexts, boundary=None, spans=())``,
  ``decode_step_bytes(...)`` with the same arguments,
  ``prefill_flops(cfg, prompt_len, boundary=None, spans=())`` and
  ``weight_bytes(cfg, boundary=None, spans=())``: the work the algorithm
  needs, for the readers in ``bench/metrics/``.  ``boundary`` is the
  traced :class:`bench.loop.Boundary` the step ran in and ``spans`` the
  program's spans that end within it, for counts that depend on what the
  step did (which experts its tokens reached); a module that needs neither
  ignores them.
- ``SMOKE`` and ``CONTROL``: the widths the CPU tests put in place of the
  configuration's, for whole runs and for the fp8 control.
"""
