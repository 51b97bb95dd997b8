"""One module per ensemble kind, found by the configuration's ``ensemble``.

Each module gives, for its kind:

* ``train(cfg, world) -> (params, beta)``: the ensemble's weights as numpy
  arrays in original model order, trained from the world, and the
  ensemble's decision threshold on the sum of its base-model scores;
* ``scores(params, x) -> (N, T) float32``: the plain base-model scores,
  written from the model's definition and independent of the program;
* ``lower_precision(params)``: the weights the control computes with;
* ``program_scorer(params)``: the program's ``StageScorer`` for the
  same weights, the one thing of the program the module names;
* ``model_ops(cfg)`` / ``model_param_bytes(cfg)``: the least work of one
  base model, with its derivation (used by the roofline and mfu readers);
  a number where every base model does the same work, or a length-T array
  in original model order where they differ (``bench/work.py`` charges
  each row the plan's first models up to its exit step).

And optionally, each with a default that is what a kind without it gets:

* ``world(cfg) -> world.World``: the rows the kind is trained, calibrated
  and served on, from a seed in the configuration; ``pool`` and
  ``x_train`` may be integer arrays, such as token ids.  Default: the
  synthetic world of ``bench/world.py`` from ``cfg["world"]``.
* ``fit_settings(cfg, T) -> dict``: further keyword arguments for
  ``api.fit`` (``optimize_order``, ``order``, ``costs``), such as an order
  pinned to depth with a cost per position.  Default: ``{}``, so QWYC
  optimises the order at unit cost.
* ``rounding_band(cfg) -> (rel_tol, max_ambiguous_share, why)``: the band
  around a threshold, as a share of ``Σ|f|``, within which the check does
  not compare a row, the most of the answered rows that may fall in it,
  and the reason for the band, printed with every run.  Default:
  ``harness.DEFAULT_BAND``, ``reference.REL_TOL`` and 1%.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"ensembles.{name}")
