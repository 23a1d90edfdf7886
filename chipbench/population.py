"""The inputs of a cell, made from ``--seed``: the mixture's means, the
round's population of devices, and the pool of late-device reports.

The mixture follows the paper's Section 4.1 construction (k Gaussian
components with a minimum pairwise mean distance ``sep``; index groups of
k' components, each group's data split over m0 devices). The means and
the round's population are made on the device, each in one jitted call.
The pool's sizes and component subsets are drawn on the host; its points
are made on the device in one jitted call and copied back, since the
service takes host arrays.

Every seed gets the same multiset of report sizes and local cluster
counts (drawn once from a fixed generator), in another order, so that a
seed changes the data and not the amount of work. A backlog's pool holds
that multiset in every run of ``per_flush`` consecutive reports, so each
of its flushes serves the same mix.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` 31-bit integers derived from any non-negative ``seed``
    (larger than 32 bits is fine)."""
    return (np.random.SeedSequence(int(seed)).generate_state(n)
            & 0x7FFFFFFF).astype(np.int64)


@partial(jax.jit, static_argnames=("k", "d"))
def mixture_means(key, *, k: int, d: int, sep: float):
    """k means in R^d, rescaled so the smallest pairwise distance is
    ``sep``."""
    mu = jax.random.normal(key, (k, d), jnp.float32)
    sq = jnp.sum(mu * mu, axis=1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
        mu, mu.T, precision=jax.lax.Precision.HIGHEST)
    d2 = d2 + jnp.eye(k, dtype=jnp.float32) * 1e30
    return mu * (sep / jnp.sqrt(jnp.maximum(jnp.min(d2), 1e-24)))


@partial(jax.jit, static_argnames=("k", "k_prime", "m0", "n_per_comp"))
def round_population(key, means, *, k: int, k_prime: int, m0: int,
                     n_per_comp: int, sigma: float):
    """The round's (Z, n, d) devices: Z = (k / k') * m0; device z of group
    g holds ``n_per_comp`` points of each of the k' components of g."""
    groups = k // k_prime
    Z, n = groups * m0, k_prime * n_per_comp
    group = jnp.repeat(jnp.arange(groups), m0)
    comp = jnp.tile(jnp.repeat(jnp.arange(k_prime), n_per_comp), (Z, 1))
    labels = group[:, None] * k_prime + comp
    noise = jax.random.normal(key, (Z, n, means.shape[1]), jnp.float32)
    return means[labels] + noise * sigma


@jax.jit
def _points(key, means, labels, sigma):
    noise = jax.random.normal(key, (labels.shape[0], means.shape[1]),
                              jnp.float32)
    return means[labels] + noise * sigma


def draw_sizes(spec: dict, count: int, rng: np.random.Generator
               ) -> np.ndarray:
    """Report sizes (points per device) from a size spec:
    ``{"dist": "gamma", "mean", "sd", "lo", "hi"}`` or
    ``{"dist": "fixed", "value"}``."""
    dist = spec["dist"]
    if dist == "gamma":
        mean, sd = float(spec["mean"]), float(spec["sd"])
        raw = rng.gamma((mean / sd) ** 2, sd * sd / mean, count)
        n = np.rint(raw)
    elif dist == "fixed":
        n = np.full(count, int(spec["value"]))
    else:
        raise ValueError(f"unknown size distribution {dist!r}")
    lo, hi = int(spec.get("lo", 1)), int(spec.get("hi", 1 << 40))
    return np.clip(n, lo, hi).astype(np.int64)


class Pool(NamedTuple):
    """Late-device reports: item i is ``points[start[i]:start[i] +
    size[i]]`` with ``kv[i]`` local clusters; ``comp`` holds each point's
    generating component (what the reference is checked against)."""
    points: np.ndarray      # (N, d) f32
    comp: np.ndarray        # (N,) int
    start: np.ndarray       # (P,)
    size: np.ndarray        # (P,)
    kv: np.ndarray          # (P,)

    def item(self, i: int):
        lo = int(self.start[i])
        return self.points[lo:lo + int(self.size[i])], int(self.kv[i])

    def __len__(self) -> int:
        return int(self.size.shape[0])


def make_pool(config: dict, traffic: dict, means, key, rng) -> Pool:
    """The cell's pool of late-device reports: sizes from the
    configuration's ``late_devices.n`` spec; the number of local clusters
    k^(z) uniform in [kv_min, k']."""
    k, kp = config["plan"]["k"], config["plan"]["k_prime"]
    late = config["late_devices"]
    count = int(traffic["pool"])
    block = int(traffic.get("per_flush", count))
    if count % block:
        raise ValueError(f"pool {count} is not a multiple of the "
                         f"{block} reports per flush")
    fixed = np.random.default_rng(0)
    size = np.tile(draw_sizes(late["n"], block, fixed), count // block)
    kv = np.tile(fixed.integers(int(late.get("kv_min", 1)), kp + 1, block),
                 count // block)
    order = np.concatenate([lo + rng.permutation(block)
                            for lo in range(0, count, block)])
    size, kv = size[order], kv[order]
    comp = np.concatenate([
        rng.choice(rng.choice(k, int(v), replace=False), int(n))
        for n, v in zip(size, kv)])
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    pts = _points(key, means, jnp.asarray(comp, jnp.int32),
                  jnp.float32(config["population"]["sigma"]))
    return Pool(np.asarray(pts), comp, start, size, kv)
