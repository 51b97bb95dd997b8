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
  base model, with its derivation (used by the roofline and mfu readers).
"""

import importlib


def load(name: str):
    return importlib.import_module(f"ensembles.{name}")
