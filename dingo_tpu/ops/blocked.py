"""Dimension-blocked (PDX-style vertical) scan layout helpers.

PDX (PAPERS.md) stores vectors *vertically* — all candidates' values for
one block of dimensions contiguously — so a scan can accumulate partial
distances one dimension-block at a time and drop candidates whose partial
distance already cannot beat the running k-th best. The TPU translation
(ops/pallas_ivf.ivf_probe_scan / ops/pallas_topk.pruned_fused_search):

  * data      [n_blocks, n, block_d]  (FLAT store mirror; the IVF bucket
              arrays stay [B, cap, d] — a BlockSpec (1, cap, block_d) tile
              IS the vertical access pattern, no physical copy needed)
  * bsq       [n_blocks, n] f32       per-dimension-block squared norms of
              the (decoded) rows — the metadata both pruning bounds need:
              L2 partial  = qpsq[j] - 2*cumdot + xpsq[j]   (lower bound of
                            the final distance: remaining blocks add >= 0)
              IP  bound   = cumdot + sqrt(qtail[j] * xtail[j])
                            (Cauchy-Schwarz on the unseen suffix)

Blocking is pure reshape/transpose (+ zero-padding of the trailing
partial block), so flat <-> blocked round-trips are bit-exact; zero pads
contribute 0 to every block norm and every partial dot, so scores are
unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def resolve_dim_block(dim: int, dim_block: Optional[int] = None
                      ) -> Optional[int]:
    """Effective dimension-block width for an index, or None when blocking
    cannot pay: pruning needs >= 2 blocks, and the kernels require the
    dimension to tile exactly (a partial trailing block would need masked
    DMA — zero-pad the *storage* instead, see pad_dim)."""
    if dim_block is None:
        from dingo_tpu.common.config import FLAGS

        dim_block = int(FLAGS.get("ivf_dim_block"))
    if dim_block <= 0:
        return None
    if dim % dim_block or dim // dim_block < 2:
        return None
    return dim_block


def n_blocks(dim: int, dim_block: int) -> int:
    return -(-dim // dim_block)


def pad_dim(dim: int, dim_block: int) -> int:
    """Storage dimension rounded up to a whole number of blocks."""
    return n_blocks(dim, dim_block) * dim_block


def to_blocked(rows, dim_block: int):
    """[n, d] -> [n_blocks, n, block_d] (zero-padded trailing block).

    Works for numpy and jax arrays; the transform is a transpose of a
    reshape, so from_blocked(to_blocked(x)) == x bit-for-bit."""
    xp = jnp if isinstance(rows, jax.Array) else np
    n, d = rows.shape
    nblk = n_blocks(d, dim_block)
    pad = nblk * dim_block - d
    if pad:
        rows = xp.concatenate(
            [rows, xp.zeros((n, pad), rows.dtype)], axis=1
        )
    return xp.transpose(
        rows.reshape(n, nblk, dim_block), (1, 0, 2)
    )


def from_blocked(blk, dim: int):
    """[n_blocks, n, block_d] -> [n, d] (strips dimension padding)."""
    xp = jnp if isinstance(blk, jax.Array) else np
    nblk, n, dblk = blk.shape
    return xp.transpose(blk, (1, 0, 2)).reshape(n, nblk * dblk)[:, :dim]


def block_sqnorms(rows, dim_block: int):
    """Per-dimension-block squared norms [n_blocks, n] f32 of f32-ish rows
    (callers decode sq8 codes first — bounds must describe what the scan
    kernel actually accumulates)."""
    xp = jnp if isinstance(rows, jax.Array) else np
    blk = to_blocked(xp.asarray(rows, xp.float32), dim_block)
    return (blk * blk).sum(axis=2)


def bucket_block_sqnorms(data: jax.Array, dim_block: int) -> jax.Array:
    """[A, cap, d] bucket data -> per-block norms [A, n_blocks, cap] f32
    (the IVF view's pruning metadata, built at materialize time)."""
    a, cap, d = data.shape
    nblk = n_blocks(d, dim_block)
    pad = nblk * dim_block - d
    x = data.astype(jnp.float32)
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((a, cap, pad), jnp.float32)], axis=2
        )
    x = x.reshape(a, cap, nblk, dim_block)
    return jnp.transpose((x * x).sum(axis=3), (0, 2, 1))


def query_prefix_sqnorms(q: jax.Array, dim_block: int) -> jax.Array:
    """Inclusive per-block prefix norms [b, n_blocks] f32:
    out[:, j] = sum_{j' <= j} ||q_block_j'||^2 (out[:, -1] == ||q||^2).
    The L2 partial bound reads the prefix; the IP bound derives the
    suffix as ||q||^2 - prefix."""
    b, d = q.shape
    nblk = n_blocks(d, dim_block)
    pad = nblk * dim_block - d
    x = q.astype(jnp.float32)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((b, pad), jnp.float32)], axis=1)
    per = (x.reshape(b, nblk, dim_block) ** 2).sum(axis=2)
    return jnp.cumsum(per, axis=1)
