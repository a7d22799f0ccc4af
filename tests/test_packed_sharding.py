"""Tensor-parallel specs for packed serving + segmented-scan fast paths.

The spec tests use a duck-typed mesh (``Planner`` only reads
``axis_names``/``shape`` to compute PartitionSpecs), so the fast tier
needs no fake devices. The decode parity test at the bottom needs a
real >= 2 device runtime and skips on one device —
``scripts/tier1.sh distributed`` runs this file under 2 fake CPU
devices.
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.packed_model import (LR_SHARD_RANK, PACKED_VARIANTS,
                                     PackedStack, layer_slice_range,
                                     merge_packed_axes, pack_linear,
                                     packed_axes, packed_linear_axes)
from repro.core.slab import SLaBDecomposition
from repro.core.sparsity import prune_mask
from repro.models import lm
from repro.models.common import positions_for
from repro.runtime.sharding import Planner

from benchmarks.common import synthetic_pruned_packed

N, K = 64, 128
_HAS_LOWRANK = ("slab-nm", "slab-ell", "slab-dense", "binlr",
                "lowrank-nm", "lowrank-ell", "lowrank-dense", "lowrank")


class FakeMesh(NamedTuple):
    """Duck-typed stand-in: Planner.spec only reads these two fields."""
    axis_names: tuple
    shape: dict


MESH24 = FakeMesh(("data", "model"), {"data": 2, "model": 4})


def _dec(seed, variant, rank, pattern="2:4"):
    """One synthetic decomposition that classifies as ``variant``
    (mirrors the construction of the cross-variant parity sweep)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(ks[0], (N, K), jnp.float32) * 0.1
    if variant in ("binlr", "lowrank"):
        w_s = jnp.zeros((N, K), jnp.float32)
    elif variant.endswith("-nm"):
        w_s = jnp.where(prune_mask(jnp.abs(w), 0.4, pattern=pattern),
                        w, 0.0)
    else:
        keep = 0.4 if variant.endswith("-ell") else 0.75
        w_s = jnp.where(prune_mask(jnp.abs(w), keep), w, 0.0)
    if rank:
        u = jax.random.normal(ks[1], (N, rank), jnp.float32) * 0.2
        v = jax.random.normal(ks[2], (K, rank), jnp.float32) * 0.2
    else:
        u = jnp.zeros((N, 0), jnp.float32)
        v = jnp.zeros((K, 0), jnp.float32)
    if variant.startswith("slab-") or variant == "binlr":
        w_b = jnp.where(jax.random.bernoulli(ks[3], 0.5, (N, K)),
                        1, -1).astype(jnp.int8)
    else:
        w_b = jnp.zeros((0, 0), jnp.int8)
    return SLaBDecomposition(w_s, u, v, w_b)


def _out_dim(variant, name):
    """Where d_out sits in a plane: last in the N:M planes and the sign
    words (the lane axis the kernels tile), first elsewhere."""
    if name == "b_packed" or (variant.endswith("-nm")
                              and name.startswith("sparse")):
        return -1
    return 0


def _pl(variant, rank=None):
    if rank is None:
        rank = 4 if variant in _HAS_LOWRANK else 0
    pattern = "2:4" if variant.endswith("-nm") else None
    pl = pack_linear(_dec(0, variant, rank, pattern or "2:4"), pattern)
    assert pl.variant == variant, (pl.variant, variant)
    return pl


# ---------------------------------------------------------------------------
# per-variant logical-axes trees
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", PACKED_VARIANTS)
def test_axes_tree_every_variant(variant):
    """Every stored plane except v carries packed_out at its d_out dim;
    aux matches the array leaf exactly so tree_map pairs the two
    structurally."""
    pl = _pl(variant)
    ax = packed_linear_axes(pl)
    for name in ("sparse_vals", "sparse_idx", "b_packed", "u"):
        arr, a = getattr(pl, name), getattr(ax, name)
        assert (arr is None) == (a is None), name
        if arr is not None:
            assert len(a) == arr.ndim, (name, a, arr.shape)
            if name != "u":
                assert a[_out_dim(variant, name)] == "packed_out", (name, a)
    if pl.v is not None:
        assert ax.v[0] is None           # contracts replicated features
    assert (ax.variant, ax.d_in, ax.d_out, ax.rank) == (
        pl.variant, pl.d_in, pl.d_out, pl.rank)
    # the stacked form prepends the never-sharded scan axis
    st = jax.tree.map(lambda a: a[None], pl)
    ax_st = packed_linear_axes(st, stacked=True)
    if pl.sparse_vals is not None:
        assert ax_st.sparse_vals[0] == "layers"
        at = _out_dim(variant, "sparse_vals")
        assert ax_st.sparse_vals[at if at < 0 else at + 1] == "packed_out"


def test_u_shards_only_at_rank_threshold():
    lo, hi = _pl("lowrank", rank=LR_SHARD_RANK - 1), _pl(
        "lowrank", rank=LR_SHARD_RANK)
    assert packed_linear_axes(lo).u[0] is None
    assert packed_linear_axes(hi).u[0] == "packed_out"
    assert packed_linear_axes(hi).v[0] is None


# ---------------------------------------------------------------------------
# Planner specs (duck-typed mesh, no devices needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", PACKED_VARIANTS)
def test_planner_spec_every_variant(variant):
    """tree_specs pairs the axes-PackedLinear against the array leaf and
    shards every plane's d_out dim on "model"."""
    cfg = configs.get("stablelm_12b", smoke=True)
    pl = _pl(variant, rank=LR_SHARD_RANK if variant in _HAS_LOWRANK
             else None)
    planner = Planner(MESH24, cfg)
    specs = planner.tree_specs(packed_axes(pl), pl)
    for name in ("sparse_vals", "sparse_idx", "b_packed", "u"):
        if getattr(pl, name) is not None:
            spec = getattr(specs, name)
            assert spec[_out_dim(variant, name)] == "model", (name, spec)
    if pl.v is not None:
        assert specs.v == P(None, None)


def test_packed_stack_specs_under_model_mesh():
    """A real heterogeneous model tree: stacked group planes get
    P(None, 'model', ...), the dense remainder P(None, None, 'model')."""
    cfg = configs.get("stablelm_12b", smoke=True).with_(
        dtype=jnp.float32, n_layers=4)
    _, packed, _ = synthetic_pruned_packed(
        cfg, lambda l: 0.25 if l < 2 else 0.5, skip={(0, "attn.wq")})
    planner = Planner(MESH24, cfg)
    specs = planner.tree_specs(
        merge_packed_axes(lm.param_axes(cfg), packed), packed)
    wq = specs["layers"]["attn"]["wq"]          # PackedStack of specs
    for g in wq.groups:
        assert g.sparse_vals == P(None, "model", None)
        assert g.sparse_idx == P(None, "model", None)
    assert wq.dense == P(None, None, "model")   # layer-0 dense remainder
    # dense (non-packed) leaves keep their usual rules
    assert specs["embed"] == P("model", "data")


def test_degraded_replication_spec():
    """d_out not divisible by the model axis -> every plane replicates
    (the planner's standard fallback), while divisible paths still
    shard."""
    cfg = configs.get("stablelm_12b", smoke=True).with_(d_ff=250)
    _, packed, _ = synthetic_pruned_packed(cfg, lambda l: 0.5)
    planner = Planner(MESH24, cfg)
    specs = planner.tree_specs(
        merge_packed_axes(lm.param_axes(cfg), packed), packed)
    def vals_specs(node):
        # a homogeneous whole-depth path packs to ONE stacked
        # PackedLinear; heterogeneous paths to a PackedStack of them
        groups = node.groups if isinstance(node, PackedStack) else (node,)
        return [g.sparse_vals for g in groups]

    for s in vals_specs(specs["layers"]["mlp"]["w_gate"]):
        assert s == P(None, None, None)               # 250 % 4 != 0
    for s in vals_specs(specs["layers"]["attn"]["wq"]):
        assert s == P(None, "model", None)


# ---------------------------------------------------------------------------
# segment pre-slicing (trivial-depth overhead shave)
# ---------------------------------------------------------------------------

def _hetero_stack(cfg):
    _, packed, _ = synthetic_pruned_packed(
        cfg, lambda l: 0.25 if l < 2 else 0.5, skip={(0, "attn.wq")})
    return packed["layers"]["attn"]["wq"]


def test_segment_returns_cached_identity():
    cfg = configs.get("stablelm_12b", smoke=True).with_(
        dtype=jnp.float32, n_layers=4)
    wq = _hetero_stack(cfg)
    assert isinstance(wq, PackedStack)
    a = wq.segment(2, 4)
    assert wq.segment(2, 4) is a               # memoized
    # a full-group run passes the stored stack through unsliced
    for gi, mem in enumerate(wq.members):
        lo, hi = min(mem), max(mem) + 1
        if tuple(range(lo, hi)) == mem:
            assert wq.segment(lo, hi) is wq.groups[gi]


def test_layer_slice_full_range_identity():
    cfg = configs.get("stablelm_12b", smoke=True).with_(
        dtype=jnp.float32, n_layers=4)
    _, packed, _ = synthetic_pruned_packed(cfg, lambda l: 0.5)
    layers = packed["layers"]
    assert layer_slice_range(layers, 0, cfg.n_layers) is not None
    sliced = layer_slice_range(layers, 0, cfg.n_layers)
    for a, b in zip(jax.tree.leaves(layers), jax.tree.leaves(sliced)):
        assert a is b                          # no copies at full range


def test_length_one_segments_skip_scan():
    """Per-layer segments at depth 2 run the body directly: the decode
    jaxpr contains no scan over the layer axis (trace counts stay one
    body per segment — test_segmented_scan pins that invariant)."""
    cfg = configs.get("stablelm_12b", smoke=True).with_(
        dtype=jnp.float32, n_layers=2)
    _, packed, _ = synthetic_pruned_packed(cfg, lambda l: 0.5)
    cache = lm.init_cache(cfg, 1, 2)
    tok = jnp.zeros((1, 1), jnp.int32)
    pos = positions_for(cfg, 1, 1)
    jaxpr = jax.make_jaxpr(
        lambda c, t, p: lm.decode_step(cfg, packed, c, t, p,
                                       segments=((0, 1), (1, 2))))(
        cache, tok, pos)
    assert "scan" not in str(jaxpr.jaxpr)


# ---------------------------------------------------------------------------
# decode parity under a real mesh (scripts/tier1.sh distributed)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (tier1.sh distributed)")
def test_mesh_decode_parity_two_devices():
    from repro.runtime.meshctx import use_mesh

    cfg = configs.get("stablelm_12b", smoke=True).with_(
        dtype=jnp.float32, n_layers=2)
    _, packed, _ = synthetic_pruned_packed(cfg, lambda l: 0.5)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 2), ("data", "model"))
    planner = Planner(mesh, cfg)
    placed = jax.device_put(packed, planner.tree_shardings(
        merge_packed_axes(lm.param_axes(cfg), packed), packed))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 3), 0, cfg.vocab)

    def dec(params, m):
        with use_mesh(m):
            cache = lm.init_cache(cfg, 2, 3)
            step = jax.jit(
                lambda c, t, p: lm.decode_step(cfg, params, c, t, p))
            for t in range(3):
                logits, cache = step(cache, toks[:, t:t + 1],
                                     positions_for(cfg, 2, 1, offset=t))
        return np.asarray(jax.device_get(logits))

    np.testing.assert_allclose(dec(placed, mesh), dec(packed, None),
                               rtol=2e-4, atol=2e-4)
