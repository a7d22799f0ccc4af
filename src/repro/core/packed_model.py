"""Packed-weight model serving: every compressed linear lives in an
on-HBM packed format (N:M values+indices, row-padded ELL, bit-packed
W_B, rank-r u/v factors) and forwards through the fused Pallas kernels.

``PackedLinear`` is a **variant-tagged** registered pytree: the arrays
that exist depend on which decomposition terms the compressor produced,
and a static ``variant`` tag picks the kernel at dispatch time:

  variant          terms                       kernel
  ---------------  --------------------------  ---------------------------
  slab-nm          N:M W_S + W_B + rank-r UV   ops.slab_nm_matmul
  slab-ell         ELL W_S + W_B + rank-r UV   ops.slab_ell_matmul
  slab-dense       dense W_S + W_B + rank-r    ops.slab_matmul
  binlr            W_B + rank-r UV (no W_S)    ops.binlr
  lowrank-nm       N:M W_S + rank-r UV         ops.slab_nm_lr_matmul
  lowrank-ell      ELL W_S + rank-r UV         ops.ell_lr_matmul
  lowrank-dense    dense W_S + rank-r UV       ops.slab_lr_matmul
  lowrank          rank-r UV only              (x @ V) @ Uᵀ (XLA; already
                                               minimal bytes)
  sparse-nm        N:M W_S only                ops.nm_matmul
  sparse-ell       ELL W_S only                ops.ell_matmul
  sparse-dense     dense-masked W_S only       x @ W_Sᵀ (XLA; dense-masked
                                               bytes equal dense — the
                                               format tag still marks the
                                               linear as served-in-format)

Unstructured sparse parts are routed to the row-padded ELL format
(uint16 column ids, uint32 beyond 65535 columns; K_max = realized max
per-row nnz) whenever it wins on bytes — ``packing.ell_wins_bytes`` —
so unstructured SLaB / HASSLE-free / Wanda layers finally store fewer
HBM bytes than dense; the ``*-dense`` variants remain the fallback for
near-dense sparsity.

Static metadata (variant, m_pat, d_in, d_out, rank) rides in the pytree
aux data, so stacks of packed layers slice cleanly through ``lax.scan``
and ``jax.tree.map`` like any other parameter — and tree operations
refuse to mix variants (aux mismatch), which is exactly the stacking
invariant the packer enforces.

Heterogeneous paths — different variants/patterns/ranks across layers of
one path, or partial layer coverage — pack into a ``PackedStack``:
per-signature stacks keyed by the full packed signature (variant aux +
leaf shapes, so e.g. two ELL groups with different K_max never stack)
plus an optional stacked dense remainder. A PackedStack cannot slice
through ONE ``lax.scan`` (leaf shapes differ per layer), but the layer
axis always partitions into maximal contiguous runs with identical
per-path signatures — ``segment_runs`` — and each run scans: ``models.
lm`` drives one ``lax.scan`` per segment (`layer_slice_range` emits the
per-segment stacked leaves), so a mixed plan on an L-layer model traces
O(#segments) layer bodies instead of O(L).

CPU note: Mosaic only compiles on TPU; on CPU the kernels run in
interpret mode (numerics-exact, slow) — the packed path is exercised by
tests/examples at smoke scale and is the TPU serving configuration.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import types
import warnings
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.packing import (ell_pack, ell_row_nnz_max, ell_wins_bytes,
                                pack_nm, pack_sign_bits)
from repro.core.slab import SLaBDecomposition
from repro.models.common import is_axes_leaf, scope, tap_record

Array = jax.Array

PACKED_VARIANTS = ("slab-nm", "slab-ell", "slab-dense", "binlr",
                   "lowrank-nm", "lowrank-ell", "lowrank-dense", "lowrank",
                   "sparse-nm", "sparse-ell", "sparse-dense")

# Rank threshold for sharding the low-rank u factor on "model": below
# this the (D_out, r) plane is a few KB and replicating it beats paying
# a collective for the rank-r correction; at/above it u row-shards with
# the other d_out planes. v (D_in, r) always replicates — it contracts
# against the (replicated) input features.
LR_SHARD_RANK = 8


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedLinear:
    """One compressed linear, model-orientation (computes x @ Wᵀ for the
    paper's (D_out, D_in) W — i.e. a drop-in for x @ w, w (D_in, D_out)).

    Array fields are pytree children (absent terms are None); the
    variant tag and shape metadata are static aux data, preserved by
    stacking/slicing and checked for equality by tree operations.

    sparse_vals : (D_out, D_in) dense-masked W_S, or (n, D_in/m, D_out)
                  N:M values, or (D_out, K_max) ELL values, or None.
    sparse_idx  : (n, D_in/m, D_out) int8 N:M positions, or
                  (D_out, K_max) uint16 ELL column ids, or None.
    b_packed    : (D_in/32, D_out) uint32 sign bits, or None.
    u, v        : (D_out, r) / (D_in, r) low-rank factors, or None.
    """

    sparse_vals: Optional[Array]
    sparse_idx: Optional[Array]
    b_packed: Optional[Array]
    u: Optional[Array]
    v: Optional[Array]
    variant: str = "slab-dense"
    m_pat: int = 0            # N:M group size m (0 = not N:M)
    d_in: int = 0
    d_out: int = 0
    rank: int = 0

    def tree_flatten(self):
        return ((self.sparse_vals, self.sparse_idx, self.b_packed,
                 self.u, self.v),
                (self.variant, self.m_pat, self.d_in, self.d_out,
                 self.rank))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedStack:
    """Signature-grouped packed stacks for one linear path across the
    layer dim.

    ``groups[g]`` is a PackedLinear stacked over ``members[g]`` (layer
    ids, ascending); ``dense`` is the original stacked weight restricted
    to ``dense_members`` — layers the plan left dense (partial
    coverage). Membership is static aux data so ``at_layer`` /
    ``segment`` resolve at trace time; the model scans contiguous
    same-signature layer runs of one of these (``segment_runs``)."""

    groups: Tuple[PackedLinear, ...]
    dense: Optional[Array]
    members: Tuple[Tuple[int, ...], ...]
    dense_members: Tuple[int, ...]
    n_layers: int

    def tree_flatten(self):
        return ((self.groups, self.dense),
                (self.members, self.dense_members, self.n_layers))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def owner_group(self, l: int) -> int:
        """Index of the group holding layer ``l`` (-1 = dense remainder)."""
        for gi, mem in enumerate(self.members):
            if l in mem:
                return gi
        if l in self.dense_members:
            return -1
        raise KeyError(f"layer {l} not held by this PackedStack")

    def at_layer(self, l: int):
        """The layer-``l`` leaf: a sliced PackedLinear or a dense 2-D
        weight (in model (D_in, D_out) orientation)."""
        leaf = self.segment(l, l + 1)
        return jax.tree.map(lambda a: a[0], leaf)

    def _seg_cache(self) -> dict:
        """Per-instance memo of pre-sliced segment leaves. Lives outside
        the pytree (plain attribute on the frozen dataclass), so slicing
        each run happens ONCE per stack instance instead of at every
        trace — the scan body then carries no layer-axis slicing at all.
        Tracer leaves are never cached (a stack passed as a jit argument
        would otherwise leak its tracers past the trace)."""
        c = self.__dict__.get("_segcache")
        if c is None:
            c = {}
            object.__setattr__(self, "_segcache", c)
        return c

    def segment(self, lo: int, hi: int):
        """The stacked leaf for the contiguous layer run [lo, hi): a
        (hi-lo)-stacked PackedLinear or dense weight stack. The run must
        lie inside ONE group (or the dense remainder) — guaranteed for
        runs produced by ``segment_runs``; membership tuples are sorted,
        so in-group runs are contiguous slices of the stacked arrays.
        A run covering an entire group returns that group's stack
        unsliced (identity — no copy), and concrete slices are memoized
        per instance (``_seg_cache``)."""
        cache = self._seg_cache()
        out = cache.get((lo, hi))
        if out is not None:
            return out
        gi = self.owner_group(lo)
        if gi < 0:
            i = self.dense_members.index(lo)
            if self.dense_members[i:i + hi - lo] != tuple(range(lo, hi)):
                raise ValueError(f"layers [{lo},{hi}) straddle groups")
            out = (self.dense if len(self.dense_members) == hi - lo
                   else self.dense[i:i + hi - lo])
        else:
            mem = self.members[gi]
            i = mem.index(lo)
            if mem[i:i + hi - lo] != tuple(range(lo, hi)):
                raise ValueError(f"layers [{lo},{hi}) straddle groups")
            out = (self.groups[gi] if len(mem) == hi - lo
                   else jax.tree.map(lambda a: a[i:i + hi - lo],
                                     self.groups[gi]))
        if not any(isinstance(a, jax.core.Tracer)
                   for a in jax.tree.leaves(out)):
            cache[(lo, hi)] = out
        return out

    def variant_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for grp, mem in zip(self.groups, self.members):
            out[grp.variant] = out.get(grp.variant, 0) + len(mem)
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ExpertPackedStack:
    """Signature-grouped packed stacks for one 3-D MoE leaf across the
    EXPERT dim — the expert-axis analogue of ``PackedStack``'s layer
    grouping.

    ``groups[g]`` is a PackedLinear whose every plane carries a leading
    expert dim over ``members[g]`` (expert ids, ascending). Experts
    group by full packed signature; for ELL variants the per-expert
    realized K_max is first quantized into buckets
    (``pack_expert_stack``) and each bucket pads to ITS realized max —
    ragged experts never pad to the global max. ``dense`` holds the
    original model-orientation ``(E_d, D_in, D_out)`` slices for
    experts with no packable terms. One kernel launch serves a whole
    bucket (``expert_matmul``: the per-linear kernel under ``jax.vmap``,
    the expert index leading the Pallas grid).

    Layer stacking is structural: a stacked ExpertPackedStack simply
    carries an extra leading layer dim on every child (groups' planes
    ``(L, E_g, ...)``, dense ``(L, E_d, D_in, D_out)``), so it slices
    through ``lax.scan`` / ``layer_slice_range`` like any packed leaf
    and nests as a PackedStack group when per-layer bucketings differ.
    """

    groups: Tuple[PackedLinear, ...]
    dense: Optional[Array]
    members: Tuple[Tuple[int, ...], ...]
    dense_members: Tuple[int, ...]
    n_experts: int

    def tree_flatten(self):
        return ((self.groups, self.dense),
                (self.members, self.dense_members, self.n_experts))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def variant_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for grp, mem in zip(self.groups, self.members):
            out[grp.variant] = out.get(grp.variant, 0) + len(mem)
        return out


def _is_packed_leaf(x) -> bool:
    return isinstance(x, (PackedLinear, PackedStack, ExpertPackedStack))


def has_hetero(tree) -> bool:
    """True if any leaf is a PackedStack (forces the segmented layer
    loop; homogeneous stacked PackedLinears scan as one segment)."""
    return any(isinstance(l, PackedStack)
               for l in jax.tree.leaves(tree, is_leaf=_is_packed_leaf))


def layer_slice(tree, l: int):
    """Slice a stacked layers tree at layer ``l``, resolving PackedStack
    leaves to their layer-``l`` representation."""
    def f(x):
        if isinstance(x, PackedStack):
            return x.at_layer(l)
        if isinstance(x, (PackedLinear, ExpertPackedStack)):
            return jax.tree.map(lambda a: a[l], x)
        return x[l]
    return jax.tree.map(f, tree, is_leaf=_is_packed_leaf)


# ------------------------------------------------------------------
# Contiguous-segment scan groups
# ------------------------------------------------------------------

def segment_runs(tree, n_layers: int) -> Tuple[Tuple[int, int], ...]:
    """Partition the layer axis into maximal contiguous runs [lo, hi)
    with identical packed signatures: within a run, every PackedStack
    leaf stays inside one of its groups (or its dense remainder), so
    ``layer_slice_range`` yields per-segment stacked leaves with
    layer-invariant structure and one ``lax.scan`` drives the whole
    run. A fully homogeneous tree is the single run ((0, L),)."""
    stacks = [l for l in jax.tree.leaves(tree, is_leaf=_is_packed_leaf)
              if isinstance(l, PackedStack)]
    owners = [[s.owner_group(l) for s in stacks] for l in range(n_layers)]
    runs: List[Tuple[int, int]] = []
    lo = 0
    for l in range(1, n_layers):
        if owners[l] != owners[l - 1]:
            runs.append((lo, l))
            lo = l
    runs.append((lo, n_layers))
    return tuple(runs)


def layer_slice_range(tree, lo: int, hi: int):
    """Restrict a stacked layers tree to the contiguous run [lo, hi),
    resolving PackedStack leaves to their per-segment stacked form.
    Every leaf keeps a leading layer dim of hi-lo, so the result scans.
    A run spanning a leaf's full layer axis passes it through unsliced
    (identity — the homogeneous one-segment path copies nothing)."""
    def f(x):
        if isinstance(x, PackedStack):
            return x.segment(lo, hi)
        if isinstance(x, (PackedLinear, ExpertPackedStack)):
            leaves = jax.tree.leaves(x)
            if lo == 0 and leaves and leaves[0].shape[0] == hi:
                return x
            return jax.tree.map(lambda a: a[lo:hi], x)
        if lo == 0 and x.shape[0] == hi:
            return x
        return x[lo:hi]
    return jax.tree.map(f, tree, is_leaf=_is_packed_leaf)


# ------------------------------------------------------------------
# Logical axes for the sharding planner (tensor-parallel serving)
# ------------------------------------------------------------------

def _stack_depth(pl: PackedLinear) -> int:
    """0 for a per-layer PackedLinear, 1 for a layer-stacked one."""
    if pl.sparse_vals is not None:
        base = 3 if pl.variant.endswith("-nm") else 2
        return pl.sparse_vals.ndim - base
    a = pl.u if pl.u is not None else pl.b_packed
    return a.ndim - 2


def packed_linear_axes(pl: PackedLinear, stacked: bool = False,
                       lr_shard_rank: int = LR_SHARD_RANK,
                       _lead: Optional[Tuple[str, ...]] = None
                       ) -> PackedLinear:
    """The logical-axes tree of one packed linear: a PackedLinear with
    IDENTICAL static aux whose children are axes tuples, so it pairs
    structurally against the array tree in ``Planner.tree_specs`` /
    ``jax.tree.map``. Every stored plane except ``v`` carries d_out —
    leading in ELL planes ``(D_out, K_max)``, dense-masked values
    ``(D_out, D_in)`` and ``u (D_out, r)``, trailing (the lane axis the
    kernels tile) in N:M values/indices ``(n, D_in/m, D_out)`` and sign
    bits ``(D_in/32, D_out)`` — so tensor parallelism is uniform d_out
    sharding on ``"packed_out"`` (-> "model"). N:M groups, sign words
    and ELL rows run along d_in and are never split by a d_out shard;
    a d_out that doesn't divide the mesh replicates via the planner's
    standard divisibility fallback (degraded-but-correct). ``u`` only
    shards at rank >= ``lr_shard_rank``; ``v (D_in, r)`` always
    replicates (it contracts the replicated input features).
    ``_lead`` overrides the leading logical axes — the expert-stacked
    variant passes ``(..., "experts")`` so expert planes prefer EP
    ("experts" -> "model") and fall back to "packed_out" row sharding
    via the planner's one-axis-per-spec rule when the bucket size
    doesn't divide the mesh."""
    lead = _lead if _lead is not None else (("layers",) if stacked else ())

    return PackedLinear(
        *_plane_axes(pl, lead, lr_shard_rank),
        variant=pl.variant, m_pat=pl.m_pat, d_in=pl.d_in,
        d_out=pl.d_out, rank=pl.rank)


def _plane_axes(pl: PackedLinear, lead: Tuple, lr_shard_rank: int
                ) -> Tuple:
    """Per-plane axes tuples in PackedLinear child order; ``lead`` is
    prepended (layer / expert stacking dims). D_out is the last dim of
    N:M and sign-bit planes and the first of the others."""
    d_out_at = -1 if pl.variant.endswith("-nm") else 0

    def ax(a, out_dim):
        if a is None:
            return None
        spec = [None] * (a.ndim - len(lead))
        if out_dim is not None:
            spec[out_dim] = "packed_out"
        return lead + tuple(spec)

    return (ax(pl.sparse_vals, d_out_at), ax(pl.sparse_idx, d_out_at),
            ax(pl.b_packed, -1),
            ax(pl.u, 0 if pl.rank >= lr_shard_rank else None),
            ax(pl.v, None))


def _expert_stack_depth(eps: ExpertPackedStack) -> int:
    """0 for a per-layer ExpertPackedStack, 1 for a layer-stacked one
    (every plane then carries layer + expert leading dims)."""
    if eps.groups:
        return _stack_depth(eps.groups[0]) - 1
    return eps.dense.ndim - 3


def expert_stack_axes(eps: ExpertPackedStack, stacked: bool = False,
                      lr_shard_rank: int = LR_SHARD_RANK
                      ) -> ExpertPackedStack:
    """Axes tree of an ExpertPackedStack: each group's planes lead with
    the expert dim ("experts" -> "model", expert parallelism) ahead of
    the usual per-plane "packed_out" rows; the dense remainder is
    model-orientation ``(E_d, D_in, D_out)``. When the bucket size
    doesn't divide the mesh, the planner's divisibility fallback drops
    "experts" and the spec row-shards on "packed_out" instead —
    degraded-but-correct, mirroring the dense-path fallbacks."""
    lead = (("layers",) if stacked else ()) + ("experts",)
    groups = tuple(packed_linear_axes(g, lr_shard_rank=lr_shard_rank,
                                      _lead=lead)
                   for g in eps.groups)
    dense = (lead + (None, "packed_out")
             if eps.dense is not None else None)
    return ExpertPackedStack(groups, dense, eps.members,
                             eps.dense_members, eps.n_experts)


def packed_stack_axes(ps: PackedStack,
                      lr_shard_rank: int = LR_SHARD_RANK) -> PackedStack:
    """Axes tree of a PackedStack: per-group stacked PackedLinear (or
    ExpertPackedStack) axes plus ``("layers", None, "packed_out")`` for
    the dense remainder (model-orientation ``(run, D_in, D_out)`` —
    output dim last; MoE remainders add an "experts" dim)."""
    groups = tuple(
        expert_stack_axes(g, stacked=True, lr_shard_rank=lr_shard_rank)
        if isinstance(g, ExpertPackedStack)
        else packed_linear_axes(g, stacked=True,
                                lr_shard_rank=lr_shard_rank)
        for g in ps.groups)
    dense = None
    if ps.dense is not None:
        dense = (("layers", "experts", None, "packed_out")
                 if ps.dense.ndim == 4 else ("layers", None, "packed_out"))
    return PackedStack(groups, dense, ps.members, ps.dense_members,
                       ps.n_layers)


def packed_axes(leaf, lr_shard_rank: int = LR_SHARD_RANK):
    """Axes tree for any packed leaf (PackedLinear, PackedStack, or
    ExpertPackedStack)."""
    if isinstance(leaf, PackedStack):
        return packed_stack_axes(leaf, lr_shard_rank)
    if isinstance(leaf, ExpertPackedStack):
        return expert_stack_axes(leaf, stacked=_expert_stack_depth(leaf) > 0,
                                 lr_shard_rank=lr_shard_rank)
    return packed_linear_axes(leaf, stacked=_stack_depth(leaf) > 0,
                              lr_shard_rank=lr_shard_rank)


def merge_packed_axes(axes_tree, params_tree):
    """Substitute per-variant packed axes subtrees into a dense logical-
    axes tree (``lm.param_axes``) wherever ``params_tree`` holds a
    packed leaf. The result feeds ``Planner.tree_specs`` /
    ``tree_shardings`` unchanged: an axes-PackedLinear node pairs
    against the array PackedLinear structurally (same aux), and its
    tuple children stop descent exactly like plain dense axes leaves."""
    def f(ax, leaf):
        if _is_packed_leaf(leaf):
            return packed_axes(leaf)
        return ax
    return jax.tree.map(f, axes_tree, params_tree, is_leaf=is_axes_leaf)


# ------------------------------------------------------------------
# Variant classification + per-linear packing
# ------------------------------------------------------------------

def _dec_rank(dec: SLaBDecomposition) -> int:
    if dec.u is None or not dec.u.size:
        return 0
    return dec.u.shape[1] if dec.u.ndim == 2 else 1


def _unstructured_kind(w_s: Array, itemsize: Optional[int] = None,
                       k_max: Optional[int] = None) -> str:
    """"ell" when row-padded ELL beats the dense bytes of this sparse
    part (uint32 ids absorb D_in beyond uint16), else "dense".
    ``itemsize`` is the SERVING value width (defaults to the dec's own
    dtype; the packer passes its pack dtype — a bf16 serve halves the
    dense bytes and tightens the ELL threshold to K_max < D_in/2).
    ``k_max`` skips the row-nnz device sync when the caller already
    paid it — otherwise pack/classification time only."""
    d_in = w_s.shape[1]
    itemsize = w_s.dtype.itemsize if itemsize is None else itemsize
    if k_max is None:
        k_max = ell_row_nnz_max(w_s)
    if ell_wins_bytes(k_max, d_in, itemsize):
        return "ell"
    return "dense"


def variant_of(dec: SLaBDecomposition, pattern: Optional[str],
               itemsize: Optional[int] = None,
               k_max: Optional[int] = None,
               has_s: Optional[bool] = None) -> Optional[str]:
    """Classify one decomposition into its packed-serving variant (None
    = not representable; stays dense). The binary term only counts when
    a low-rank factor exists — W_L ⊙ W_B with empty W_L is identically
    zero (see core.slab.low_rank_times_binary), so a lone W_B carries no
    signal and the sparse part serves alone. ``has_s`` (is the sparse
    part non-zero) skips that device sync when the caller batched it —
    ``pack_expert_stack`` classifies every expert from ONE fused
    reduction."""
    if dec.w_s is None or dec.w_s.ndim != 2:
        return None
    rank = _dec_rank(dec)
    has_b = (dec.w_b is not None and dec.w_b.size > 0 and rank > 0)
    if not has_b and rank == 0:
        # pruning-only dec: the sparse part is the only term — route it
        # to ELL when that wins on bytes (an all-zero W_S packs as a
        # width-1 ELL serving zeros, same as its dense equivalent)
        kind = ("nm" if pattern
                else _unstructured_kind(dec.w_s, itemsize, k_max))
        return f"sparse-{kind}"
    if has_s is None:
        has_s = bool(dec.w_s.size) and bool(jnp.any(dec.w_s != 0))
    kind = (("nm" if pattern
             else _unstructured_kind(dec.w_s, itemsize, k_max))
            if has_s else None)
    if has_b:
        return f"slab-{kind}" if kind else "binlr"
    if rank > 0:
        return f"lowrank-{kind}" if kind else "lowrank"
    return f"sparse-{kind}" if kind else None


def pack_linear(dec: SLaBDecomposition, pattern: Optional[str],
                dtype=jnp.float32,
                variant: Optional[str] = None,
                ell_nnz: Optional[int] = None) -> PackedLinear:
    """Pack one decomposition into its variant's storage format.
    ``ell_nnz`` overrides the ELL pad width K_max (callers that already
    synced the row-nnz reduction, or that stack several layers at one
    shared width, pass it to skip the recompute)."""
    d_out, d_in = dec.w_s.shape
    if variant is None:
        variant = variant_of(dec, pattern,
                             itemsize=jnp.dtype(dtype).itemsize,
                             k_max=ell_nnz)
    if variant is None:
        raise ValueError("decomposition has no packable terms")
    rank = _dec_rank(dec)
    u = v = bp = vals = idx = None
    m_pat = 0
    if rank:
        u = (dec.u if dec.u.ndim == 2 else dec.u[:, None]).astype(dtype)
        v = (dec.v if dec.v.ndim == 2 else dec.v[:, None]).astype(dtype)
    if variant.startswith("slab-") or variant == "binlr":
        bp = pack_sign_bits(dec.w_b)
    if variant.endswith("-nm"):
        n, m_pat = map(int, pattern.split(":"))
        # strict: a rule pattern that disagrees with the compressor's
        # actual output must fail loudly, not drop values
        nm = pack_nm(dec.w_s.astype(dtype), n, m_pat, strict=True)
        vals, idx = nm.values, nm.indices
    elif variant.endswith("-ell"):
        ep = ell_pack(dec.w_s.astype(dtype), nnz=ell_nnz)
        vals, idx = ep.values, ep.indices
    elif variant.endswith("-dense") or variant.startswith("sparse"):
        vals = dec.w_s.astype(dtype)
    return PackedLinear(vals, idx, bp, u, v, variant=variant, m_pat=m_pat,
                        d_in=d_in, d_out=d_out, rank=rank)


def _pick_block(dim: int, cap: int, mult: int = 1) -> int:
    """Largest block ≤ cap that divides ``dim`` and is a multiple of
    ``mult`` — collapses the grid to one step whenever the axis fits
    (the dominant cost at decode/smoke shapes is per-grid-step, not
    per-element). Falls back to the full axis (single block)."""
    if dim <= cap:
        return dim
    for b in range(cap, 0, -1):
        if dim % b == 0 and b % mult == 0:
            return b
    return dim


def _out_rows(w: PackedLinear) -> int:
    """The D_out extent the planes actually hold: the global d_out, or
    one device's shard of it inside ``packed_matmul``'s shard_map (the
    static ``d_out`` aux always names the global width)."""
    if w.sparse_vals is not None:
        return w.sparse_vals.shape[-1 if w.variant.endswith("-nm") else -2]
    if w.b_packed is not None:
        return w.b_packed.shape[-1]
    return w.u.shape[-2]


def _kernel_blocks(w: PackedLinear) -> Tuple[int, int]:
    """(bn, bk) for the K-gridded kernels. Every streamed plane keeps
    D_out on lanes, so bn is a multiple of 128 or the whole axis; bk
    keeps the (bk/32, bn) sign-word tile in whole (8, 128) uint32 tiles
    (bk % 256) and the (n, bk/m, bn) int8 index tile in whole (32, 128)
    tiles (bk % 32m) — the tiling the chip's compiler demands."""
    mult = 256 if w.b_packed is not None else 1
    if w.m_pat:
        mult = math.lcm(mult, 32 * w.m_pat)
    return (_pick_block(_out_rows(w), 256, 128),
            _pick_block(w.d_in, 1024, mult))


def packed_matmul(x: Array, w: PackedLinear,
                  interpret: Optional[bool] = None) -> Array:
    """x (..., D_in) @ Wᵀ through the variant's fused kernel.

    Under a multi-device mesh the kernel runs inside ``shard_map`` (the
    chip's compiler cannot partition a Pallas kernel): each device
    streams its d_out shard of every plane and writes its slice of the
    output features, which come back sharded on "model" — the packed
    tensor-parallel layout. A d_out that doesn't divide the "model"
    axis runs replicated (degraded-but-correct)."""
    from repro.runtime.meshctx import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return _packed_matmul_local(x, w, interpret)
    from jax.sharding import PartitionSpec as P
    n_model = dict(mesh.shape).get("model", 1)
    split = n_model > 1 and w.d_out % n_model == 0

    def spec(ax):
        return None if ax is None else P(*(
            "model" if (a == "packed_out" and split) else None for a in ax))

    w_specs = PackedLinear(*(spec(a) for a in _plane_axes(w, (), 0)),
                           variant=w.variant, m_pat=w.m_pat, d_in=w.d_in,
                           d_out=w.d_out, rank=w.rank)
    out = P(*(None,) * (x.ndim - 1), "model" if split else None)
    return jax.shard_map(
        lambda xx, ww: _packed_matmul_local(xx, ww, interpret),
        mesh=mesh, in_specs=(P(), w_specs), out_specs=out,
        check_vma=False)(x, w)


def _packed_matmul_local(x: Array, w: PackedLinear,
                         interpret: Optional[bool]) -> Array:
    from repro.kernels import ops
    var = w.variant
    bn, bk = _kernel_blocks(w)
    if var.endswith("-ell"):
        kw = dict(bm=128, bn=bn, interpret=interpret)
        if var == "sparse-ell":
            y = ops.ell_matmul(x, w.sparse_vals, w.sparse_idx, **kw)
        elif var == "lowrank-ell":
            y = ops.ell_lr_matmul(x, w.sparse_vals, w.sparse_idx,
                                  w.u, w.v, **kw)
        else:
            y = ops.slab_ell_matmul(x, w.sparse_vals, w.sparse_idx,
                                    w.b_packed, w.u, w.v, **kw)
        return y.astype(x.dtype)
    kw = dict(bm=128, bn=bn, bk=bk, interpret=interpret)
    if var == "slab-nm":
        y = ops.slab_nm_matmul(x, w.sparse_vals, w.sparse_idx, w.m_pat,
                               w.b_packed, w.u, w.v, **kw)
    elif var == "slab-dense":
        y = ops.slab_matmul(x, w.sparse_vals.astype(x.dtype), w.b_packed,
                            w.u, w.v, **kw)
    elif var == "binlr":
        y = ops.binlr(x, w.b_packed, w.u, w.v, **kw)
    elif var == "lowrank-nm":
        y = ops.slab_nm_lr_matmul(x, w.sparse_vals, w.sparse_idx, w.m_pat,
                                  w.u, w.v, **kw)
    elif var == "lowrank-dense":
        y = ops.slab_lr_matmul(x, w.sparse_vals.astype(x.dtype),
                               w.u, w.v, **kw)
    elif var == "lowrank":
        # two skinny XLA matmuls: r(D_in + D_out) weights per token —
        # already the minimal-byte form, nothing left to fuse
        y = (x.astype(jnp.float32) @ w.v.astype(jnp.float32)) \
            @ w.u.astype(jnp.float32).T
    elif var == "sparse-nm":
        y = ops.nm_matmul(x, w.sparse_vals, w.sparse_idx, w.m_pat, **kw)
    elif var == "sparse-dense":
        # dense-masked bytes equal dense bytes: a plain dot IS the
        # optimal serve; the tag records the linear as served-in-format
        y = x @ w.sparse_vals.astype(x.dtype).T
    else:
        raise ValueError(f"unknown packed variant {var!r}")
    return y.astype(x.dtype)


def expert_matmul(x: Array, w: ExpertPackedStack,
                  interpret: Optional[bool] = None) -> Array:
    """Per-expert packed linear: x (E, M, D_in) -> (E, M, D_out).

    One kernel launch per expert BUCKET: experts of a bucket share
    packed shapes (same variant / rank / ELL pad width), so each launch
    streams a contiguous (E_g, ...) plane stack. The launch is the
    per-linear dispatch under ``jax.vmap`` over the bucket's leading
    expert dim: Pallas's batching rule puts the expert index at the
    front of the kernel's grid, and the static aux rides through as it
    does for ``lax.scan``. Expert ids are static aux, so the bucket
    gathers/reorder resolve to constant-index gathers at trace time;
    the common all-in-one-bucket case skips them entirely."""
    per_expert = jax.vmap(
        lambda xx, ww: _packed_matmul_local(xx, ww, interpret))
    n = w.n_experts
    if (len(w.groups) == 1 and not w.dense_members
            and w.members[0] == tuple(range(n))):
        return per_expert(x, w.groups[0])
    parts: List[Array] = []
    order: List[int] = []
    for mem, grp in zip(w.members, w.groups):
        xg = jnp.take(x, jnp.asarray(mem), axis=0)
        parts.append(per_expert(xg, grp))
        order.extend(mem)
    if w.dense is not None:
        xd = jnp.take(x, jnp.asarray(w.dense_members), axis=0)
        parts.append(jnp.einsum("emk,ekn->emn", xd,
                                w.dense.astype(x.dtype)).astype(x.dtype))
        order.extend(w.dense_members)
    y = jnp.concatenate(parts, axis=0)
    inv = [0] * n
    for pos, eid in enumerate(order):
        inv[eid] = pos
    return jnp.take(y, jnp.asarray(inv), axis=0)


def linear(x: Array, w, tap: Optional[str] = None) -> Array:
    """Dispatch point used by the model layers: dense `x @ w` or the
    packed fused kernel. ``tap`` names this linear for activation
    capture (models.common.tap_capture): when a capture is active the
    exact input ``x`` is reported under the current tap scope before
    the matmul runs; otherwise it's a no-op. The matmul's ops carry the
    named scope ``tap``."""
    if tap is not None:
        tap_record(tap, x)
    with scope(tap) if tap is not None else contextlib.nullcontext():
        if isinstance(w, PackedLinear):
            return packed_matmul(x, w)
        return x @ w


# ------------------------------------------------------------------
# Whole-model packing
# ------------------------------------------------------------------

class Segment(NamedTuple):
    """One contiguous same-signature layer run of a packed model."""
    lo: int
    hi: int                            # exclusive
    sig: Tuple[Tuple[str, str], ...]   # (path, descriptor) per packed path


class PackReport(NamedTuple):
    """What pack_plan_decs did: per-variant packed-linear counts, the
    packed paths, the (layer, path) decs left on the dense path, the
    contiguous scan segments, and per-variant packed-vs-dense bytes."""
    n_packed: int
    by_variant: Dict[str, int]
    paths: List[str]
    fallback: List[Tuple[int, str]]
    segments: Tuple[Segment, ...] = ()
    bytes_by_variant: Mapping[str, Tuple[float, float]] = \
        types.MappingProxyType({})   # immutable: defaults never alias a
                                     # mutable dict across instances


def _stack_group(pls: List[PackedLinear]) -> PackedLinear:
    if len(pls) == 1:
        return jax.tree.map(lambda a: a[None], pls[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *pls)


def _pack_signature(pl: PackedLinear) -> Tuple:
    """Full stacking key: static aux + per-leaf (shape, dtype). Groups
    may only stack layers whose arrays are congruent — e.g. two ELL
    layers with different realized K_max get distinct signatures."""
    aux = (pl.variant, pl.m_pat, pl.d_in, pl.d_out, pl.rank)
    leaves = tuple((None if a is None else (a.shape, str(a.dtype)))
                   for a in (pl.sparse_vals, pl.sparse_idx, pl.b_packed,
                             pl.u, pl.v))
    return aux + leaves


def _describe(pl) -> str:
    if isinstance(pl, ExpertPackedStack):
        parts = [f"{_describe(jax.tree.map(lambda a: a[0], g))} x{len(m)}"
                 for g, m in zip(pl.groups, pl.members)]
        if pl.dense_members:
            parts.append(f"dense x{len(pl.dense_members)}")
        return "experts[" + " | ".join(parts) + "]"
    d = pl.variant
    if pl.m_pat:
        d += f"({pl.sparse_vals.shape[-3]}:{pl.m_pat})"
    elif pl.variant.endswith("-ell"):
        d += f"(kmax={pl.sparse_vals.shape[-1]})"
    if pl.rank:
        d += f" r{pl.rank}"
    return d


def _leaf_signature(leaf) -> Tuple:
    """Layer-stacking key for any per-layer packed leaf."""
    if isinstance(leaf, ExpertPackedStack):
        return (("experts", leaf.members, leaf.dense_members,
                 leaf.n_experts)
                + tuple(_pack_signature(g) for g in leaf.groups)
                + ((None if leaf.dense is None
                    else (leaf.dense.shape, str(leaf.dense.dtype))),))
    return _pack_signature(leaf)


# How many quantization buckets the per-expert realized ELL K_max is
# split into: within a bucket experts pad to the bucket's realized max,
# so a few hot experts don't inflate every expert's pad width, while
# the number of per-bucket kernel launches stays bounded.
EXPERT_KMAX_BUCKETS = 4


def pack_expert_stack(old: Array,
                      e_decs: Tuple[SLaBDecomposition, ...],
                      pattern: Optional[str],
                      dtype=jnp.float32,
                      n_buckets: int = EXPERT_KMAX_BUCKETS
                      ) -> ExpertPackedStack:
    """Pack one layer's 3-D MoE leaf from its per-expert decompositions.

    ``old`` is the model-orientation ``(E, D_in, D_out)`` expert leaf
    (kept for unservable experts' dense slices); ``e_decs`` the
    per-expert paper-orientation decs the pipeline produced. All
    experts classify from ONE fused device sync (per-expert realized
    row-nnz K_max + total nnz); ELL experts then bucket by quantized
    K_max — bucket width ``ceil(global_max / n_buckets)`` — and every
    bucket pads to its own realized max. Experts sharing a full packed
    signature stack into one kernel launch (``expert_matmul``)."""
    n_exp = len(e_decs)
    itemsize = jnp.dtype(dtype).itemsize
    # experts with no sparse plane at all (w_s=None decs) can't join the
    # fused nnz sync — they classify straight to the dense remainder
    servable = [e for e, d in enumerate(e_decs)
                if d.w_s is not None and d.w_s.ndim == 2]
    kmaxes = [1] * n_exp
    variants: List[Optional[str]] = [None] * n_exp
    if servable:
        ws = jnp.stack([e_decs[e].w_s for e in servable])
        row_nnz, tot_nnz = jax.device_get(
            (jnp.max(jnp.sum(ws != 0, axis=-1), axis=-1),
             jnp.sum(ws != 0, axis=(1, 2))))
        for i, e in enumerate(servable):
            kmaxes[e] = max(1, int(row_nnz[i]))
            variants[e] = variant_of(e_decs[e], pattern, itemsize,
                                     k_max=kmaxes[e],
                                     has_s=bool(tot_nnz[i]))
    q = max(1, -(-max(kmaxes) // n_buckets))
    pads: Dict[int, int] = {}
    for e, var in enumerate(variants):
        if var is not None and var.endswith("-ell"):
            b = (kmaxes[e] - 1) // q
            pads[b] = max(pads.get(b, 0), kmaxes[e])
    by_sig: Dict[Tuple, List[Tuple[int, PackedLinear]]] = {}
    dense_members: List[int] = []
    for e, (dec, var) in enumerate(zip(e_decs, variants)):
        if var is None:
            dense_members.append(e)
            continue
        nnz = (pads[(kmaxes[e] - 1) // q] if var.endswith("-ell")
               else kmaxes[e])
        pl = pack_linear(dec, pattern, dtype, variant=var, ell_nnz=nnz)
        by_sig.setdefault(_pack_signature(pl), []).append((e, pl))
    groups: List[PackedLinear] = []
    members: List[Tuple[int, ...]] = []
    for key in sorted(by_sig, key=str):
        es = by_sig[key]
        groups.append(_stack_group([pl for (_, pl) in es]))
        members.append(tuple(e for (e, _) in es))
    dense = (jnp.stack([old[e] for e in dense_members])
             if dense_members else None)
    return ExpertPackedStack(tuple(groups), dense, tuple(members),
                             tuple(dense_members), n_exp)


def _model_segments(layers_tree, n_layers: int,
                    paths: List[str]) -> Tuple[Segment, ...]:
    """The contiguous scan segments of a packed layers tree plus, per
    segment, the (path, variant descriptor) signature serve.py prints."""
    from repro.core.pipeline import _get
    segs = []
    for lo, hi in segment_runs(layers_tree, n_layers):
        sig = []
        for p in paths:
            leaf = _get(layers_tree, p)
            if isinstance(leaf, PackedStack):
                gi = leaf.owner_group(lo)
                desc = ("dense" if gi < 0
                        else _describe(jax.tree.map(lambda a: a[0],
                                                    leaf.groups[gi])))
            else:
                desc = _describe(jax.tree.map(lambda a: a[0], leaf))
            sig.append((p, desc))
        segs.append(Segment(lo, hi, tuple(sig)))
    return tuple(segs)


def pack_plan_decs(params: dict,
                   decs: Dict[Tuple[int, str], SLaBDecomposition],
                   n_layers: int, plan,
                   dtype=jnp.float32,
                   variants: Optional[Dict[Tuple[int, str], str]] = None,
                   planner=None
                   ) -> Tuple[dict, PackReport]:
    """Pack EVERY servable decomposition of a (possibly mixed-method)
    plan — mixed variants, mixed N:M patterns, mixed ranks, and partial
    layer coverage per path all pack:

      * layers of one path with the same packed signature (variant aux
        + array shapes) stack into one scan-sliceable group;
      * a path whose single group covers all layers stays a plain
        stacked PackedLinear (one-scan fast path);
      * anything else becomes a PackedStack of signature groups plus
        the dense remainder, and the model scans the maximal contiguous
        same-signature layer runs (``segment_runs``).

    Patterns come from each dec's own resolved plan rule (per (layer,
    path) — not layer 0's), so paths whose early layers are skipped or
    use different rules pack fine. ``variants`` optionally supplies the
    per-(layer, path) classification the pipeline already computed
    (``CompressStats.variant``; "" = unservable) so the per-linear
    ``variant_of`` device sync isn't paid twice.

    ``planner`` (a ``runtime.sharding.Planner``) makes packing mesh-
    aware: each packed leaf is placed with the NamedShardings of its
    per-variant axes tree (``packed_axes``) the moment it is built —
    leaves are *born sharded* instead of replicated then resharded —
    and the per-segment slice cache is warmed after placement, so the
    pre-sliced scan inputs carry the shards too.

    3-D MoE leaves arrive as TUPLES of per-expert decs (the pipeline's
    expert branch) and pack into per-layer ``ExpertPackedStack``s
    (one kernel launch per K_max bucket); hybrid shared-block decs
    arrive under ``shared.*`` names (keyed at the firing layer) and
    pack once into ``params["shared_attn"]``. Still-dense bytes —
    unservable decs, plan-uncovered layers of packed paths, and
    unservable experts — aggregate under the ``"dense-fallback"``
    pseudo-variant so the bytes summary reflects true model bytes for
    partially packed models. Returns (params, PackReport); a warning is
    emitted for any packed variant whose measured bytes exceed its
    dense footprint."""
    from repro.core.pipeline import _get, _set

    pack_itemsize = jnp.dtype(dtype).itemsize
    by_path: Dict[str, Dict[Tuple,
                            List[Tuple[int, PackedLinear]]]] = {}
    expert_by_path: Dict[str, Dict[int, ExpertPackedStack]] = {}
    shared_pls: List[Tuple[int, str, PackedLinear]] = []
    fallback: List[Tuple[int, str]] = []
    n_packed = 0
    by_variant: Dict[str, int] = {}
    bytes_by_variant: Dict[str, List[float]] = {}

    def _agg(var: str, packed_b: float, dense_b: float, n: int = 1):
        a = bytes_by_variant.setdefault(var, [0.0, 0.0, 0])
        a[0] += packed_b
        a[1] += dense_b
        a[2] += n

    for (l, name) in sorted(decs, key=lambda k: (k[1], k[0])):
        dec = decs[(l, name)]
        r = plan.resolve(l, name)
        pattern = r.scfg.pattern if r is not None else None
        # a plain tuple of per-expert decs marks a 3-D MoE leaf
        # (SLaBDecomposition itself is a NamedTuple — exact type check)
        if type(dec) is tuple:
            old = _get(params["layers"], name)
            if old is None:
                fallback.append((l, name))
                continue
            expert_by_path.setdefault(name, {})[l] = \
                pack_expert_stack(old[l], dec, pattern, dtype)
            continue
        # the row-nnz device sync is LAZY: a pipeline-supplied dense-kind
        # variant at matching dtypes pays zero extra syncs, and an
        # ELL-routed linear pays exactly one (shared by the dtype
        # revalidation and ell_pack's pad width)
        k_max = None
        if variants is not None and (l, name) in variants:
            var = variants[(l, name)] or None
            if (var is not None and var.endswith(("-ell", "-dense"))
                    and dec.w_s.dtype.itemsize != pack_itemsize):
                # the pipeline classified at the dec's own dtype; the
                # ELL-vs-dense bytes race depends on the PACK dtype
                k_max = ell_row_nnz_max(dec.w_s)
                base = var.rsplit("-", 1)[0]
                var = (f"{base}-"
                       f"{_unstructured_kind(dec.w_s, pack_itemsize, k_max)}")
        else:
            var = variant_of(dec, pattern, itemsize=pack_itemsize)
        if var is None:
            fallback.append((l, name))
            continue
        if var.endswith("-ell") and k_max is None:
            k_max = ell_row_nnz_max(dec.w_s)
        pl = pack_linear(dec, pattern, dtype, variant=var,
                         ell_nnz=k_max if var.endswith("-ell") else None)
        if name.startswith("shared."):
            shared_pls.append((l, name, pl))
            continue
        by_path.setdefault(name, {}).setdefault(
            _pack_signature(pl), []).append((l, pl))

    out = jax.tree.map(lambda a: a, params)     # shallow copy
    packed_paths: List[str] = []
    for name, groups in sorted(by_path.items()):
        old = _get(out["layers"], name)
        if old is None:
            fallback.extend((l, name) for vs in groups.values()
                            for (l, _) in vs)
            continue
        per_dense = old.nbytes / old.shape[0]
        stacked_groups: List[PackedLinear] = []
        members: List[Tuple[int, ...]] = []
        for key in sorted(groups, key=str):
            layers = groups[key]
            var = layers[0][1].variant
            stacked_groups.append(_stack_group([pl for (_, pl) in layers]))
            members.append(tuple(l for (l, _) in layers))
            by_variant[var] = by_variant.get(var, 0) + len(layers)
            n_packed += len(layers)
            for (_, pl) in layers:
                _agg(var, sum(a.nbytes for a in jax.tree.leaves(pl)),
                     per_dense)
        covered = {l for mem in members for l in mem}
        missing = tuple(l for l in range(n_layers) if l not in covered)
        if not missing and len(stacked_groups) == 1:
            leaf = stacked_groups[0]            # one-scan fast path
        else:
            dense = (jnp.stack([old[l] for l in missing])
                     if missing else None)
            leaf = PackedStack(tuple(stacked_groups), dense,
                               tuple(members), missing, n_layers)
            if missing:
                _agg("dense-fallback", per_dense * len(missing),
                     per_dense * len(missing), len(missing))
        if planner is not None:
            # pack AFTER placement: the leaf materializes with its
            # per-variant NamedShardings rather than being replicated
            # first and resharded by the first constrained step
            leaf = jax.device_put(
                leaf, planner.tree_shardings(packed_axes(leaf), leaf))
        _set(out["layers"], name, leaf)
        packed_paths.append(name)

    # ---- expert-axis (3-D MoE) paths ----
    for name, per_layer in sorted(expert_by_path.items()):
        old = _get(out["layers"], name)
        per_dense_e = old.nbytes / (old.shape[0] * old.shape[1])
        by_sig: Dict[Tuple, List[Tuple[int, ExpertPackedStack]]] = {}
        for l, eps in sorted(per_layer.items()):
            for grp, mem in zip(eps.groups, eps.members):
                var = grp.variant
                by_variant[var] = by_variant.get(var, 0) + len(mem)
                n_packed += len(mem)
                _agg(var, sum(a.nbytes for a in jax.tree.leaves(grp)),
                     per_dense_e * len(mem), len(mem))
            for e in eps.dense_members:
                fallback.append((l, f"{name}[expert {e}]"))
                _agg("dense-fallback", per_dense_e, per_dense_e)
            by_sig.setdefault(_leaf_signature(eps), []).append((l, eps))
        stacked_groups = []
        members = []
        for key in sorted(by_sig, key=str):
            ls = by_sig[key]
            stacked_groups.append(_stack_group([e for (_, e) in ls]))
            members.append(tuple(l for (l, _) in ls))
        covered = {l for mem in members for l in mem}
        missing = tuple(l for l in range(n_layers) if l not in covered)
        if not missing and len(stacked_groups) == 1:
            leaf = stacked_groups[0]            # one-scan fast path
        else:
            dense = (jnp.stack([old[l] for l in missing])
                     if missing else None)
            leaf = PackedStack(tuple(stacked_groups), dense,
                               tuple(members), missing, n_layers)
            if missing:
                n_e = old.shape[1]
                _agg("dense-fallback", per_dense_e * n_e * len(missing),
                     per_dense_e * n_e * len(missing), n_e * len(missing))
        if planner is not None:
            leaf = jax.device_put(
                leaf, planner.tree_shardings(packed_axes(leaf), leaf))
        _set(out["layers"], name, leaf)
        packed_paths.append(name)

    # ---- hybrid shared-block paths (packed once, outside the stack) ----
    for l, name, pl in sorted(shared_pls, key=lambda t: t[1]):
        sub = name.split(".", 1)[1]
        old = _get(out.get("shared_attn", {}), sub)
        if old is None:
            fallback.append((l, name))
            continue
        if planner is not None:
            pl = jax.device_put(
                pl, planner.tree_shardings(packed_axes(pl), pl))
        _set(out["shared_attn"], sub, pl)
        by_variant[pl.variant] = by_variant.get(pl.variant, 0) + 1
        n_packed += 1
        _agg(pl.variant, sum(a.nbytes for a in jax.tree.leaves(pl)),
             float(old.nbytes))
        packed_paths.append(name)

    # unservable decs stayed dense: their bytes count toward the model too
    for (l, fname) in fallback:
        base = fname.split("[", 1)[0]
        if base.startswith("shared."):
            w = _get(out.get("shared_attn", {}), base.split(".", 1)[1])
            if w is not None and not isinstance(w, PackedLinear):
                _agg("dense-fallback", float(w.nbytes), float(w.nbytes))
        elif "[expert " not in fname:           # expert slices counted above
            wp = _get(params["layers"], base)
            if wp is not None:
                _agg("dense-fallback", wp.nbytes / wp.shape[0],
                     wp.nbytes / wp.shape[0])

    per_linear = {var: (p / n, d / n)
                  for var, (p, d, n) in bytes_by_variant.items()}
    for var, (p, d) in sorted(per_linear.items()):
        if p > d:
            warnings.warn(
                f"packed variant {var!r} stores {p / d:.2f}x its dense "
                f"bytes ({p / 1e3:.1f} kB vs {d / 1e3:.1f} kB per linear)"
                " — this format loses on the serving roofline",
                stacklevel=2)
    layer_paths = [p for p in packed_paths if not p.startswith("shared.")]
    segments = _model_segments(out["layers"], n_layers, layer_paths)
    # pre-slice every (stack, run) pair once, at pack time: decode-step
    # traces then reuse the memoized (and, under a planner, sharded)
    # segment leaves instead of re-slicing the layer axis per trace
    stacks = [l for l in jax.tree.leaves(out["layers"],
                                         is_leaf=_is_packed_leaf)
              if isinstance(l, PackedStack)]
    for seg in segments:
        for s in stacks:
            s.segment(seg.lo, seg.hi)
    return out, PackReport(n_packed, by_variant, packed_paths,
                           sorted(fallback, key=lambda k: (k[1], k[0])),
                           segments, per_linear)


def pack_model(params: dict,
               decs: Dict[Tuple[int, str], SLaBDecomposition],
               n_layers: int,
               pattern: Optional[str] = None,
               dtype=jnp.float32) -> dict:
    """Single-pattern convenience packer: replace each fully-covered
    decomposed path in the stacked-params tree with a stacked
    PackedLinear (partial-coverage paths are skipped — use
    ``pack_plan_decs`` for the general mixed/partial case). ``decs``
    comes from core.pipeline.compress_model (keep_decompositions=True)."""
    from repro.core.pipeline import _get, _set
    out = jax.tree.map(lambda a: a, params)     # shallow copy
    paths = sorted({p for (_, p) in decs})
    itemsize = jnp.dtype(dtype).itemsize
    for path in paths:
        if any((l, path) not in decs for l in range(n_layers)):
            continue                             # partial coverage: skip
        if any(type(decs[(l, path)]) is tuple for l in range(n_layers)):
            continue         # 3-D expert tuples need pack_plan_decs
        variants = [variant_of(decs[(l, path)], pattern, itemsize)
                    for l in range(n_layers)]
        if len(set(variants)) != 1 or variants[0] is None:
            continue                             # mixed variants: skip
        # ELL layers of one path pack at the shared per-path K_max so
        # ragged realized widths still stack (a few pad columns beat
        # silently losing the whole path to dense)
        ell_nnz = None
        if variants[0].endswith("-ell"):
            ell_nnz = max(ell_row_nnz_max(decs[(l, path)].w_s)
                          for l in range(n_layers))
        per_layer = [pack_linear(decs[(l, path)], pattern, dtype,
                                 variant=variants[l], ell_nnz=ell_nnz)
                     for l in range(n_layers)]
        if len({_pack_signature(pl) for pl in per_layer}) != 1:
            continue                             # incongruent terms: skip
        _set(out["layers"], path, _stack_group(per_layer))
    return out
