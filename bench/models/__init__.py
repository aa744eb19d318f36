"""Model families, one module each, found by a configuration's ``model`` key:
``bench/models/<cfg["model"]>.py``, as paths and metric readers are found by
name.  A module gives everything the harness knows of its family:

* ``plan(cfg)``: ``(shape, scale)`` per leaf, in the program's layout
  (`bench.weights` draws the weights from it);
* ``train_flops_per_token(cfg, seq)``: forward + backward FLOPs per token
  (per sample, for a task without tokens), no recompute (`bench.work`);
* ``node_batch(cfg, traffic)``: (tokens or samples, bytes) of one node's
  batch in one tick (`bench.work.tick_counts`);
* ``node_grad(cfg, dtype)``: the plain reference's ``(params, batch) ->
  (loss, grads)`` for one node, from ``bench/reference/``, which imports
  nothing of the program;
* the program's side: ``program_config(cfg, dtype)``, the program's
  ``ModelConfig``, for a language model (the stream and mesh paths build it
  through `repro.models.api`; its ``dtype`` is also the control's lower
  precision in `bench/calibrate.py`); ``program_init(cfg)`` and
  ``program_grad_fn(cfg)`` for a flat task (the flat path).

A path module is about the trainer; it takes the model from here.
"""
from __future__ import annotations

import importlib


def of(cfg: dict):
    """The module of the configuration's model family."""
    return importlib.import_module(f"{__name__}.{cfg['model']}")
