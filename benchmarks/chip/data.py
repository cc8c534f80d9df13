"""Vectors and queries for a cell, made from the run's seed.

The base set is the clustered-Gaussian generator the repository uses for
SIFT-shaped data (copied here so that no later change to ``src/`` moves
the yardstick): cluster centres ~ U[0, 1]^D, rows ~ N(centre, spread).
It is drawn from the configuration's fixed ``base_seed``.

The run's seed then does two things:

* it flips the sign of a seed-chosen subset of the D coordinates of every
  row and query.  A sign flip is an exact isometry in floating point:
  every squared distance, dot product, norm and symmetric int8 code the
  program computes is bitwise the same as on the base set, so every seed
  gives the same partition sizes, padded shapes and compiled programs,
  while the numbers the program reads differ from seed to seed;
* it draws the query pool: ``queries`` perturbed copies of seed-chosen
  rows (``query_noise * spread`` of Gaussian noise), as the repository's
  generator makes its queries.  A configuration with ``"pool_seed":
  "base"`` draws the pool from ``base_seed`` instead (and flips it with
  the rows), so that every seed serves the same queries: where the
  traffic repeats popular queries, which ones are popular decides the
  work.
"""
from __future__ import annotations

import numpy as np


def base_vectors(rows: int, dim: int, *, n_clusters: int, spread: float,
                 base_seed: int) -> np.ndarray:
    """(rows, dim) float32 clustered Gaussians from ``base_seed``."""
    rng = np.random.default_rng(base_seed)
    centers = rng.random((n_clusters, dim), dtype=np.float32)
    assign = rng.integers(0, n_clusters, size=rows)
    noise = rng.standard_normal((rows, dim), dtype=np.float32)
    noise *= np.float32(spread)
    noise += centers[assign]
    return noise


def seed_signs(dim: int, seed: int) -> np.ndarray:
    """(dim,) float32 of +-1, one draw per seed."""
    rng = np.random.default_rng([seed, 1])
    return np.where(rng.random(dim) < 0.5, -1.0, 1.0).astype(np.float32)


def query_pool(data: np.ndarray, n_queries: int, *, spread: float,
               query_noise: float, seed: int) -> np.ndarray:
    """(n_queries, dim) float32: perturbed copies of seed-chosen rows."""
    rng = np.random.default_rng([seed, 2])
    src = rng.integers(0, data.shape[0], size=n_queries)
    noise = rng.standard_normal((n_queries, data.shape[1]), dtype=np.float32)
    return data[src] + np.float32(query_noise * spread) * noise


def make(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(data, queries) of configuration ``cfg`` for run seed ``seed``."""
    data = base_vectors(cfg["rows"], cfg["dim"], n_clusters=cfg["n_clusters"],
                        spread=cfg["spread"], base_seed=cfg["base_seed"])
    signs = seed_signs(cfg["dim"], seed)
    base_pool = cfg.get("pool_seed") == "base"
    if not base_pool:
        data *= signs
    queries = query_pool(data, cfg["queries"], spread=cfg["spread"],
                         query_noise=cfg["query_noise"],
                         seed=cfg["base_seed"] if base_pool else seed)
    if base_pool:
        data *= signs
        queries *= signs
    return data, queries
