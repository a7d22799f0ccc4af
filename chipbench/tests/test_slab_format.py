"""The seeded SLaB weight builder at smoke width: the program packs every
linear to slab-nm with no dense fallback, the packed planes unpack to
the decomposition, and the reference's dense equivalent is its
reconstruction."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights, work
from chipbench.formats import slab_nm

CFG = {"hidden_size": 128, "intermediate_size": 256,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32}
FMT = {"kind": "slab_nm", "pattern": "2:4", "cr": 0.5, "rank": 1}


@pytest.fixture(scope="module")
def built():
    key = weights.root_key(2 ** 40 + 3)
    shapes = work.linear_shapes(CFG)
    make = lambda l: slab_nm.make_layer(weights.layer_key(key, l), shapes,
                                        FMT)
    return make, slab_nm.program_linears(make, 2, FMT)


def test_every_linear_packs_slab_nm(built):
    _, packed = built
    from repro.core.packed_model import PackedLinear
    assert sorted(packed) == sorted(work.linear_shapes(CFG))
    for leaf in packed.values():
        assert isinstance(leaf, PackedLinear)
        assert leaf.variant == "slab-nm" and leaf.m_pat == 4
        assert leaf.sparse_vals.shape[0] == 2          # stacked layers


def test_unpacked_planes_equal_the_decomposition(built):
    from repro.core.packing import NMPacked, unpack_nm, unpack_sign_bits
    from repro.core.slab import SLaBDecomposition, reconstruct
    make, packed = built
    for l in range(2):
        parts = make(l)
        for path, d in parts.items():
            pl = packed[path]
            d_out, d_in = d["w_s"].shape
            w_s = unpack_nm(NMPacked(pl.sparse_vals[l], pl.sparse_idx[l],
                                     2, 4, d_in))
            np.testing.assert_array_equal(np.asarray(w_s, np.float32),
                                          np.asarray(d["w_s"], np.float32))
            w_b = unpack_sign_bits(pl.b_packed[l], d_in)
            np.testing.assert_array_equal(np.asarray(w_b),
                                          np.asarray(d["w_b"]))
            np.testing.assert_array_equal(np.asarray(pl.u[l]),
                                          np.asarray(d["u"]))
            # factors in f32, so the program's reconstruction does not
            # round u vᵀ to bf16
            dec = SLaBDecomposition(d["w_s"], d["u"].astype(jnp.float32),
                                    d["v"].astype(jnp.float32), d["w_b"])
            np.testing.assert_allclose(
                np.asarray(slab_nm.dense_equivalent(d)),
                np.asarray(reconstruct(dec)).T, rtol=1e-6, atol=1e-7)


def test_the_sparse_part_is_exactly_2_of_4(built):
    make, _ = built
    w = np.asarray(make(0)["mlp.w_up"]["w_s"], np.float32)
    groups = (w.reshape(w.shape[0], -1, 4) != 0).sum(-1)
    assert groups.max() == 2 and groups.mean() > 1.99


def test_same_key_same_weights():
    key = weights.root_key(7)
    shapes = {"attn.wq": (128, 128)}
    a = slab_nm.make_layer(key, shapes, FMT)["attn.wq"]["w_s"]
    b = slab_nm.make_layer(key, shapes, FMT)["attn.wq"]["w_s"]
    assert jnp.array_equal(a, b)
