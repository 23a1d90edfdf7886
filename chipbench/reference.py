"""The plain reference, and the comparison that decides ``correct``.

The service gives each request a partition of the device's points into
global clusters, numbered by the tau version the request was served
under, and keeps the request's report (its local centers) in a fold
slot, from which every refresh re-finalizes tau. The configuration's
guarantees are checked against references that import nothing of the
program and take nothing it made:

* the partition (Theorem 3.2, in the separation regime the mixture is
  drawn in) is the one the generating components make: each point's
  nearest generating mean, in float64 on the host. A refresh may number
  tau's centers anew, so the served ids of each tau version are matched
  one to one with the reference's (largest overlaps first);
* the report is Algorithm 1's fixed point: where a device holds exactly
  k^(z) components, the converged local centers are the means of its
  points per component, in float64 on the host;
* the fold keeps the most recent ``capacity`` admitted reports (lru),
  each with its k^(z) valid centers at weight 1 (the configurations
  leave ``weight_by_core_counts`` off); tau is re-finalized once per
  ``refresh_every`` admitted folds, checked after the batch that
  crosses it, so at most ``refresh_every + batch - 1`` folds apart.

Compared, each with its limit from ``limits/<cell>.json`` (an exact
count has the limit 0):

* ``mostly_wrong_requests``: the share of requests due in the window
  whose labels miss the reference's on more than half of their points;
* ``unanswered``: requests due in the window never answered, or answered
  in the wrong form (length, range, version);
* ``misfolded``: requests the fold must still hold (the latest whole
  flushes, at most ``capacity`` requests) with no slot stamped with their
  id, or with the wrong valid centers or weights there;
* ``report_gap``: over those that hold exactly k^(z) components, the
  median of each report's gap to the reference means: the larger of the
  two directed distances between the two sets of centers, each relative
  to the mean's norm. The float32 program reads about 1e-7, its bfloat16
  path (centers stored in bfloat16) about 1e-3;
* ``refreshes_off_cadence``: how far the number of tau versions the
  window committed lies outside what the cadence allows.

Reported beside them, not compared: ``mislabeled_share`` (points),
``merged_versions`` (tau versions in which two generating components
share one center), and the counts behind the fold check. A report that
the local solve got badly wrong can become a center of its own at a
refresh, which merges two components until lru evicts it; the point
share swings with that from seed to seed (PERF.md, section 6), and the
report of such a device reads a large gap, which the median passes over.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import numpy as np


class Fold(NamedTuple):
    """What the window left in the service, read once it closed."""
    centers: np.ndarray     # (capacity, k', d) slot reports
    mask: np.ndarray        # (capacity, k') valid centers
    weights: np.ndarray     # (capacity, k')
    received: np.ndarray    # (capacity,) slot holds a report
    epoch: np.ndarray       # (capacity,) request id of the slot's report
    refreshes: int          # tau versions committed in the window
    since_refresh: int      # folds counted toward a refresh at its start


def nearest_means(points: np.ndarray, means: np.ndarray,
                  block: int = 1 << 15) -> np.ndarray:
    """Index of each point's nearest mean, in float64, block by block."""
    mu = np.asarray(means, np.float64)
    mu2 = np.sum(mu * mu, axis=1)
    out = np.empty(points.shape[0], np.int64)
    for lo in range(0, points.shape[0], block):
        x = np.asarray(points[lo:lo + block], np.float64)
        d2 = mu2[None, :] - 2.0 * (x @ mu.T)
        out[lo:lo + block] = np.argmin(d2, axis=1)
    return out


def match(served: np.ndarray, ref: np.ndarray) -> Dict[int, int]:
    """One-to-one map from served ids to reference ids, largest overlap
    first (ties by the smaller pair)."""
    pairs, counts = np.unique(np.stack([served, ref]), axis=1,
                              return_counts=True)
    ids, used = {}, set()
    for j in np.argsort(-counts, kind="stable"):
        s, r = (int(t) for t in pairs[:, j])
        if s not in ids and r not in used:
            ids[s] = r
            used.add(r)
    return ids


def merges(served: np.ndarray, ref: np.ndarray) -> bool:
    """Whether one served id holds two reference components, each with
    more than a tenth of its points."""
    for x in np.unique(served):
        counts = np.bincount(ref[served == x])
        if np.count_nonzero(counts > 0.1 * counts.sum()) > 1:
            return True
    return False


def well_formed(r: dict, k: int) -> bool:
    lab = r["labels"]
    if lab is None or not isinstance(r["version"], (int, np.integer)):
        return False
    lab = np.asarray(lab)
    return (lab.shape == (r["n"],)
            and bool(np.all((lab >= 0) & (lab < k))))


def component_means(points: np.ndarray, comp: np.ndarray) -> np.ndarray:
    """The float64 mean of the points of each component present, in the
    order of the sorted component ids."""
    ids, inv = np.unique(comp, return_inverse=True)
    sums = np.zeros((ids.size, points.shape[1]), np.float64)
    np.add.at(sums, inv, np.asarray(points, np.float64))
    return sums / np.bincount(inv)[:, None]


def set_gap(centers: np.ndarray, means: np.ndarray) -> float:
    """The larger of the two directed distances between two sets of
    centers, each distance relative to the norm of the mean it is
    measured to."""
    c = np.asarray(centers, np.float64)
    rel = (np.linalg.norm(c[:, None, :] - means[None, :, :], axis=2)
           / np.linalg.norm(means, axis=1)[None, :])
    return float(max(rel.min(axis=1).max(), rel.min(axis=0).max()))


def held(requests: List[dict], capacity: int) -> List[dict]:
    """The answered requests of the latest whole flushes, at most
    ``capacity`` of them: lru holds exactly the ``capacity`` most
    recently admitted ids, and a flush admits all of its requests after
    every earlier flush's."""
    by_flush: Dict[float, List[dict]] = {}
    for r in requests:
        if r["done"] is not None:
            by_flush.setdefault(r["flush"], []).append(r)
    out: List[dict] = []
    for start in sorted(by_flush, reverse=True):
        if len(out) + len(by_flush[start]) > capacity:
            break
        out += by_flush[start]
    return out


def fold_checks(requests: List[dict], pool, plan: dict, fold: Fold
                ) -> Tuple[dict, dict]:
    """The fold and refresh guarantees: returns the compared values
    (``misfolded``, ``report_gap``, ``refreshes_off_cadence``) and the
    counts behind them."""
    slot_of = {int(e): s for s, e in enumerate(fold.epoch)
               if fold.received[s]}
    expect = held(requests, int(plan["capacity"]))
    misfolded, gaps = 0, []
    for r in expect:
        s = slot_of.get(int(r["rid"]))
        kv = int(pool.kv[r["item"]])
        if (s is None or int(np.sum(fold.mask[s])) != kv
                or np.any(fold.weights[s][fold.mask[s]] != 1.0)):
            misfolded += 1
            continue
        lo = int(pool.start[r["item"]])
        comp = pool.comp[lo:lo + r["n"]]
        if np.unique(comp).size != kv:
            continue
        gaps.append(set_gap(fold.centers[s][fold.mask[s]],
                            component_means(pool.points[lo:lo + r["n"]],
                                            comp)))
    answered = sum(r["done"] is not None for r in requests)
    R, B = int(plan["refresh_every"]), int(plan["batch_size"])
    least = answered // (R + B - 1) if R else 0
    most = (fold.since_refresh + answered) // R if R else 0
    off = max(least - fold.refreshes, fold.refreshes - most, 0)
    values = {"misfolded": misfolded,
              "report_gap": float(np.median(gaps)) if gaps else 1.0,
              "refreshes_off_cadence": off}
    seen = {"fold_checked": len(expect), "reports_compared": len(gaps),
            "report_gap_max": max(gaps) if gaps else None,
            "refreshes": fold.refreshes, "refreshes_allowed": [least, most]}
    return values, seen


def compare(requests: List[dict], pool, means: np.ndarray, plan: dict,
            fold: Fold, limits: dict
            ) -> Tuple[bool, Dict[str, dict], Dict[str, float]]:
    """Check every request due in the window, and what the window left
    in the fold. Returns ``correct``, ``{name: {"value", "limit"}}`` for
    each number compared, and the readings reported beside them."""
    k = int(plan["k"])
    good = [r for r in requests if well_formed(r, k)]
    items = sorted({r["item"] for r in good})
    ref = {}
    if items:
        pts = np.concatenate([pool.item(i)[0] for i in items])
        lab = nearest_means(pts, means)
        ends = np.cumsum([pool.size[i] for i in items])
        for i, part in zip(items, np.split(lab, ends[:-1])):
            ref[i] = part
    wrong = total = mostly = merged = 0
    versions = sorted({int(r["version"]) for r in good})
    for v in versions:
        mine = [r for r in good if int(r["version"]) == v]
        s = np.concatenate([np.asarray(r["labels"]) for r in mine])
        t = np.concatenate([ref[r["item"]] for r in mine])
        ids = match(s, t)
        mapped = np.array([ids.get(int(x), -1) for x in range(k)])
        miss = mapped[s] != t
        wrong += int(np.sum(miss))
        total += s.size
        ends = np.cumsum([r["n"] for r in mine])[:-1]
        mostly += sum(2 * int(np.sum(m)) > m.size
                      for m in np.split(miss, ends))
        merged += merges(s, t)
    values = {"mostly_wrong_requests": mostly / max(len(good), 1),
              "unanswered": len(requests) - len(good)}
    folded, seen = fold_checks(requests, pool, plan, fold)
    values.update(folded)
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in values.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    seen.update({"mislabeled_share": wrong / max(total, 1),
                 "merged_versions": merged, "versions": len(versions)})
    return ok and bool(good), checks, seen
