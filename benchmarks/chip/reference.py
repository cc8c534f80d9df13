"""The plain reference: exact squared-L2 nearest neighbours.

Imports nothing of the program and takes nothing it made.  It runs after
the window, once the program's state is freed, in blocks so that it fits
beside nothing else on the chip.

``exact_topk``  the reference: candidates by a float32 matmul at HIGHEST
                precision, then the top ``k`` re-ranked by the exact
                float32 sum of squared differences.
``true_dists``  float64 distances of given (query, row) pairs, on the host.
``control_topk``  the reference computed one precision lower (bfloat16
                inputs and arithmetic): it stands in the program's place
                to show that the comparison catches lost precision.
"""
from __future__ import annotations

import functools

import numpy as np

SLACK = 16          # extra candidates re-ranked exactly per query


@functools.lru_cache(maxsize=None)
def _topk_fn(k: int, m: int, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    @jax.jit
    def block(q, x, x2, base):
        q = q.astype(dtype)
        x = x.astype(dtype)
        dot = jnp.matmul(q, x.T, precision=prec,
                         preferred_element_type=dtype)
        d = x2.astype(dtype)[None, :] - 2 * dot
        nd, ni = jax.lax.top_k(-d, m)
        return -nd, ni + base

    @jax.jit
    def rerank(q, x, ids):
        q = q.astype(dtype)
        rows = x[ids].astype(dtype)                       # (Q, c, D)
        d = jnp.sum(jnp.square(rows - q[:, None, :]), axis=-1)
        nd, pos = jax.lax.top_k(-d, k)
        return -nd, jnp.take_along_axis(ids, pos, axis=1)

    return block, rerank


def _topk(data_dev, queries: np.ndarray, k: int, dtype_name: str,
          q_block: int, x_block: int):
    import jax
    import jax.numpy as jnp

    m = k + SLACK
    block, rerank = _topk_fn(k, m, dtype_name)
    n = data_dev.shape[0]
    x2 = jnp.sum(jnp.square(data_dev.astype(dtype_name)), axis=1)
    out_d = np.empty((len(queries), k), np.float64)
    out_i = np.empty((len(queries), k), np.int64)
    for s in range(0, len(queries), q_block):
        qb = queries[s:s + q_block]
        pad = q_block - len(qb)
        q = jnp.asarray(np.pad(qb, ((0, pad), (0, 0))))
        cand_d, cand_i = [], []
        for b in range(0, n, x_block):
            d, i = block(q, data_dev[b:b + x_block], x2[b:b + x_block], b)
            cand_d.append(d)
            cand_i.append(i)
        d = jnp.concatenate(cand_d, axis=1)
        i = jnp.concatenate(cand_i, axis=1)
        _, pos = jax.lax.top_k(-d, m)
        ids = jnp.take_along_axis(i, pos, axis=1)
        fd, fi = jax.device_get(rerank(q, data_dev, ids))
        out_d[s:s + len(qb)] = fd[:len(qb)]
        out_i[s:s + len(qb)] = fi[:len(qb)]
    return out_d, out_i


def exact_topk(data: np.ndarray, queries: np.ndarray, k: int, *,
               q_block: int = 1024, x_block: int = 1 << 17):
    """(dists (Q, k), ids (Q, k)) of the exact nearest rows, ascending."""
    import jax
    data_dev = jax.device_put(np.asarray(data, np.float32))
    try:
        return _topk(data_dev, np.asarray(queries, np.float32), k,
                     "float32", q_block, x_block)
    finally:
        data_dev.delete()


def control_topk(data: np.ndarray, queries: np.ndarray, k: int, *,
                 q_block: int = 1024, x_block: int = 1 << 17):
    """The reference one precision lower: bfloat16 throughout."""
    import jax
    data_dev = jax.device_put(np.asarray(data, np.float32))
    try:
        return _topk(data_dev, np.asarray(queries, np.float32), k,
                     "bfloat16", q_block, x_block)
    finally:
        data_dev.delete()


def true_dists(data: np.ndarray, queries: np.ndarray, ids: np.ndarray,
               block: int = 4096) -> np.ndarray:
    """float64 squared distances of ``queries[i]`` to ``data[ids[i, j]]``;
    ids outside the data read as NaN."""
    ids = np.asarray(ids, np.int64)
    out = np.full(ids.shape, np.nan, np.float64)
    ok = (ids >= 0) & (ids < data.shape[0])
    for s in range(0, len(ids), block):
        sl = slice(s, s + block)
        safe = np.where(ok[sl], ids[sl], 0)
        rows = data[safe].astype(np.float64)
        q = np.asarray(queries[sl], np.float64)[:, None, :]
        d = np.sum(np.square(rows - q), axis=-1)
        out[sl] = np.where(ok[sl], d, np.nan)
    return out
