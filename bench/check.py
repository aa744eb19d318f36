"""The comparison that decides ``correct``.

The program's first ticks, driven through the window's own call in set-up,
against the plain float32 reference (`bench.reference.bridge`) from the
same weights and batches, once the window has closed and the program's
state is freed.  Two numbers, each with its limit (``bench/limits/<cell>.json``):

* ``loss_gap``: the largest relative gap, over the ticks followed, between
  the program's per-tick loss and the reference's.  Tick 0's loss is the
  forward pass alone; each later one follows the whole tick before it.
* ``change_gap``: over the leaves, the largest gap between the norm of the
  program's parameter change over those ticks (over the honest nodes: a
  Byzantine node's own iterate follows its lie) and the reference's, against
  the reference's norm of that leaf or of the median leaf, whichever is
  larger.  Leaves whose tick-0 reference gradient is under a thousandth of
  the median leaf's are left out: round-off alone moves them.

The norm of the first gradient cannot be read: a fused chunk returns only
the state after its last tick.  A state returned unchanged reads
``change_gap`` = 1.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.reference import bridge as ref_bridge


def compare(prog_losses, prog_change: dict, ref_losses, ref_change: dict,
            ref_grad0: dict) -> dict:
    prog_losses, ref_losses = np.asarray(prog_losses, np.float64), np.asarray(ref_losses, np.float64)
    if prog_losses.shape != ref_losses.shape:
        raise ValueError(f"{prog_losses.shape} program losses vs {ref_losses.shape} reference")
    loss_gap = float(np.max(np.abs(prog_losses - ref_losses) / np.abs(ref_losses)))
    g_med = float(np.median(list(ref_grad0.values())))
    kept = [k for k, g in ref_grad0.items() if g >= 1e-3 * g_med]
    c_med = float(np.median([ref_change[k] for k in kept]))
    change_gap = max(abs(prog_change[k] - ref_change[k]) / max(ref_change[k], c_med)
                     for k in kept)
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    return {"loss_gap": loss_gap, "change_gap": float(change_gap),
            "leaves_kept": len(kept), "leaves": len(ref_grad0)}


def reference_readings(spec: dict, params0, *, dtype=None, batches=None,
                       exchange=True) -> tuple:
    """Run the reference over the spec's check batches: (losses, change,
    grad0).  ``dtype``, ``batches`` and ``exchange`` put a control or a
    fault in the program's place (`bench/calibrate.py`)."""
    kw = {} if dtype is None else {"dtype": dtype}
    after, losses, grad0 = ref_bridge.run(
        params0, spec["batches"] if batches is None else batches,
        node_grad=spec["node_grad"](**kw), exchange=exchange, adj=spec["adj"],
        byz=spec["byz"], b=spec["b"], attack_spec=spec["attack"], lam=spec["lam"],
        t0=spec["t0"], honest=spec["honest"], nodes_per_call=spec["nodes_per_call"],
        **kw)
    start = params0 if dtype is None else jax.tree_util.tree_map(lambda a: a.astype(dtype), params0)
    change = ref_bridge.change_norms(after, start, spec["honest"])
    return losses, change, grad0
