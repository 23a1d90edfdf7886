"""Deliberately broken runs, for proving the correctness check: the
precision control and the planted faults. The benchmark's own runs never
use them; ``run.py --plant <name>`` and the tests do.

* ``bf16``: the control. The program's own lower-precision path
  (``serve_dtype="bf16"``: points, centers and tau stored in bfloat16
  with f32 accumulation) in place of the f32 the plan states.
* ``alter``: an answer altered where it is produced. The serve step
  returns the first request of every batch with each label moved to the
  next cluster id.
* ``halfbatch``: half of the batch left out. The serve step computes the
  first half of every batch and hands its answers to the second half.
* ``stale``: a step that returns its state unchanged. The fold returns
  the fold state it was given, so refreshes re-finalize the round's
  reports alone.
"""
from __future__ import annotations

import jax.numpy as jnp

NAMES = ("bf16", "alter", "halfbatch", "stale")


def plan_override(name) -> dict:
    return {"serve_dtype": "bf16"} if name == "bf16" else {}


def apply(name, sess) -> None:
    """Break ``sess``'s serve plane in place as ``name`` says."""
    if name in (None, "bf16"):
        return
    plane = sess.service.plane
    if name == "stale":
        plane.fold = lambda state, *a, **kw: state
        return
    step = plane.step

    def altered(tau, keys, data, point_mask, k_valid, **kw):
        labels, centers, cmask, weights = step(tau, keys, data, point_mask,
                                               k_valid, **kw)
        row = jnp.where(labels[0] >= 0, (labels[0] + 1) % tau.shape[0],
                        labels[0])
        return labels.at[0].set(row), centers, cmask, weights

    def half(tau, keys, data, point_mask, k_valid, **kw):
        h = data.shape[0] // 2
        dup = [jnp.concatenate([x[:h], x[:h], x[2 * h:]])
               for x in (keys, data, point_mask, k_valid)]
        return step(tau, *dup, **kw)

    plane.step = {"alter": altered, "halfbatch": half}[name]
