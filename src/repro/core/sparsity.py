"""Sparsification primitives: comparison-group top-k and N:M semi-structured masks.

The paper (SLaB §II-B2) prunes by comparing scores inside *comparison
groups* of shape ``(g_rows, g_cols)``; the default is ``(1, D_in)`` (one
group per output row), keeping ``floor(k / D_out)`` entries per group.
Semi-structured patterns (2:4 / 4:8) are applied first, then group-wise
pruning refines down to the target sparsity (§II-B2).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _exact_topk_mask_rows(scores2d: Array, k: int) -> Array:
    """Exact top-k mask per row of a 2-D score array (ties broken by index)."""
    n_groups, gsz = scores2d.shape
    if k <= 0:
        return jnp.zeros_like(scores2d, dtype=jnp.bool_)
    if k >= gsz:
        return jnp.ones_like(scores2d, dtype=jnp.bool_)
    # the k-th largest value per row, then everything above it plus the
    # lowest-index ties up to k — the set lax.top_k selects, built with
    # compares and a cumsum (a scatter of the top-k indices needs
    # several times the matrix in scratch at full model widths)
    kth = jax.lax.top_k(scores2d, k)[0][:, -1:]
    above = scores2d > kth
    tie = scores2d == kth
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=1) <= room))


def group_topk_mask(scores: Array, keep_frac: float, group: Tuple[int, int] = (1, 0)) -> Array:
    """Keep the top ``floor(keep_frac * group_size)`` scores inside each group.

    ``group=(g_rows, g_cols)``; ``0`` means "the full extent of that dim".
    Groups tile the matrix; both dims must divide evenly (all paper group
    settings do: (1, D_in), (1, D_in/32), (16, D_in), ...).
    """
    d_out, d_in = scores.shape
    g_rows = group[0] or d_out
    g_cols = group[1] or d_in
    if d_out % g_rows or d_in % g_cols:
        # paper models always tile; odd smoke geometries (e.g. d_ff=344
        # with a (16, D_in) group) shrink to the nearest divisor
        g_rows = math.gcd(g_rows, d_out)
        g_cols = math.gcd(g_cols, d_in)
    gsz = g_rows * g_cols
    k = int(math.floor(keep_frac * gsz))
    # (Do/gr, gr, Di/gc, gc) -> (Do/gr, Di/gc, gr, gc) -> (n_groups, gsz)
    s = scores.reshape(d_out // g_rows, g_rows, d_in // g_cols, g_cols)
    s = s.transpose(0, 2, 1, 3).reshape(-1, gsz)
    m = _exact_topk_mask_rows(s, k)
    m = m.reshape(d_out // g_rows, d_in // g_cols, g_rows, g_cols)
    return m.transpose(0, 2, 1, 3).reshape(d_out, d_in)


def nm_mask(scores: Array, n: int, m: int) -> Array:
    """N:M semi-structured mask: keep the n best of every m consecutive
    elements along the input (last) dimension."""
    d_out, d_in = scores.shape
    if d_in % m:
        raise ValueError(f"D_in={d_in} not divisible by m={m}")
    # one (D_out, D_in/m) plane per position in the group, so the long
    # axis stays minor: an (..., m) layout pads m to 128 lanes on a TPU.
    # An element is kept when fewer than n of its group rank ahead of
    # it (larger, or equal at a lower index) — lax.top_k's selection.
    planes = scores.reshape(d_out, d_in // m, m).transpose(2, 0, 1)
    keep = []
    for p in range(m):
        ahead = [(planes[q] > planes[p]) | ((planes[q] == planes[p]) & (q < p))
                 for q in range(m) if q != p]
        keep.append(sum(a.astype(jnp.int32) for a in ahead) < n)
    return jnp.stack(keep).transpose(1, 2, 0).reshape(d_out, d_in)


def parse_pattern(pattern: str) -> Tuple[int, int]:
    n, m = pattern.split(":")
    return int(n), int(m)


def prune_mask(
    scores: Array,
    keep_frac: float,
    group: Tuple[int, int] = (1, 0),
    pattern: Optional[str] = None,
) -> Array:
    """Full paper semantics: optional N:M pre-mask, then group top-k among
    survivors (pruned entries get a -inf score so they are never re-kept)."""
    scores = scores.astype(jnp.float32)
    if pattern is not None:
        n, m = parse_pattern(pattern)
        if keep_frac > n / m + 1e-9:
            raise ValueError(
                f"keep_frac={keep_frac:.4f} exceeds the {pattern} ceiling {n}/{m}"
            )
        pre = nm_mask(scores, n, m)
        scores = jnp.where(pre, scores, -jnp.inf)
    return group_topk_mask(scores, keep_frac, group)


def mask_nnz_per_row_uniform(mask: Array) -> Optional[int]:
    """If every row has the same nnz (true for (1, D_in) comparison groups),
    return it; else None. Used to decide ELL packability."""
    nnz = jnp.sum(mask, axis=1)
    first = int(nnz[0])
    return first if bool(jnp.all(nnz == first)) else None
