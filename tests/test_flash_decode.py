"""Flash-decode Pallas kernel: shape/dtype/length sweeps vs oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode, flash_decode_paged

CASES = [  # (B, KV, G, dh, S, bs)
    (2, 4, 3, 32, 256, 64),
    (1, 8, 4, 64, 512, 128),
    (4, 2, 12, 64, 128, 128),    # qwen2-vl-like grouping, single chunk
    (2, 1, 1, 128, 256, 64),     # MQA
]


def _mk(case, dtype=jnp.float32, seed=0):
    b, kv, g, dh, s, bs = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = (jax.random.normal(ks[0], (b, kv, g, dh), jnp.float32)
         * dh ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dh), jnp.float32).astype(dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
    return q, k, v, lengths


@pytest.mark.parametrize("case", CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=("f32", "bf16"))
def test_flash_decode_matches_ref(case, dtype):
    q, k, v, lengths = _mk(case, dtype)
    want = ref.flash_decode_ref(q, k, v, lengths)
    got = flash_decode(q, k, v, lengths, bs=case[-1], interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", CASES[:2], ids=str)
def test_flash_decode_int8(case):
    q, k, v, lengths = _mk(case)

    def quant(t):
        sc = jnp.maximum(jnp.max(jnp.abs(t), -1) / 127.0, 1e-8)
        qv = jnp.clip(jnp.round(t / sc[..., None]), -127, 127)
        return qv.astype(jnp.int8), sc

    kq, ks_ = quant(k)
    vq, vs_ = quant(v)
    want = ref.flash_decode_ref(q, kq, vq, lengths, ks_, vs_)
    got = flash_decode(q, kq, vq, lengths, ks_, vs_, bs=case[-1],
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and the quantized result tracks the exact one within int8 budget
    exact = ref.flash_decode_ref(q, k, v, lengths)
    assert float(jnp.max(jnp.abs(got - exact))) < 0.05


def _quant(t):
    sc = jnp.maximum(jnp.max(jnp.abs(t), -1) / 127.0, 1e-8)
    qv = jnp.clip(jnp.round(t / sc[..., None]), -127, 127)
    return qv.astype(jnp.int8), sc


@pytest.mark.parametrize("case", [
    (2, 2, 2, 32, 100, 32),      # s % bs != 0: final chunk padded
    (1, 4, 2, 16, 7, 32),        # bs > s: single clamped chunk
    (2, 1, 1, 16, 33, 32),       # one token past the chunk boundary
], ids=str)
def test_flash_decode_nondivisible(case):
    """s need not be a multiple of bs: the kernel pads the tail chunk
    and masks it with the valid-length predicate."""
    q, k, v, lengths = _mk(case)
    want = ref.flash_decode_ref(q, k, v, lengths)
    got = flash_decode(q, k, v, lengths, bs=case[-1], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_nondivisible_int8():
    case = (2, 2, 2, 32, 100, 32)
    q, k, v, lengths = _mk(case)
    kq, ks_ = _quant(k)
    vq, vs_ = _quant(v)
    want = ref.flash_decode_ref(q, kq, vq, lengths, ks_, vs_)
    got = flash_decode(q, kq, vq, lengths, ks_, vs_, bs=case[-1],
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_ragged_int8_parity():
    """int8 path at ragged per-row lengths (incl. length == 1)."""
    b, kv, g, dh, s, bs = 4, 2, 3, 32, 96, 32
    q, k, v, _ = _mk((b, kv, g, dh, s, bs), seed=3)
    lengths = jnp.array([1, 17, 96, 40], jnp.int32)
    kq, ks_ = _quant(k)
    vq, vs_ = _quant(v)
    want = ref.flash_decode_ref(q, kq, vq, lengths, ks_, vs_)
    got = flash_decode(q, kq, vq, lengths, ks_, vs_, bs=bs,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# Paged variant: reads K/V through per-request block tables
# ----------------------------------------------------------------------

def _scatter_to_pool(k, v, bs_blk, n_blocks, seed=0):
    """Lay contiguous (B, S, KV, dh) K/V into a shuffled head-major
    block pool (n_blocks, KV, bs, dh); returns pools and block tables."""
    b, s, kv, dh = k.shape
    n_bt = -(-s // bs_blk)
    assert n_blocks >= b * n_bt
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_blocks)[:b * n_bt].reshape(b, n_bt)
    kp = np.zeros((n_blocks, kv, bs_blk, dh), np.asarray(k).dtype)
    vp = np.zeros_like(kp)
    pad = n_bt * bs_blk - s
    kc = np.pad(np.asarray(k), ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = np.pad(np.asarray(v), ((0, 0), (0, pad), (0, 0), (0, 0)))
    for r in range(b):
        for j in range(n_bt):
            kp[perm[r, j]] = kc[r, j * bs_blk:(j + 1) * bs_blk].swapaxes(0, 1)
            vp[perm[r, j]] = vc[r, j * bs_blk:(j + 1) * bs_blk].swapaxes(0, 1)
    return (jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(perm, jnp.int32))


N_LAYERS = 3


def _stacked_pool(per_layer, bs_blk, n_blocks):
    """Stack each layer's (k, v) pools on a leading layer axis; every
    layer uses the same block table, as the engine's layers do."""
    pools = [_scatter_to_pool(k, v, bs_blk, n_blocks) for k, v in per_layer]
    return (jnp.stack([kp for kp, _, _ in pools]),
            jnp.stack([vp for _, vp, _ in pools]), pools[0][2])


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_flash_decode_paged_matches_ref(layer):
    """Each layer of a stacked pool matches the reference on its own
    K/V; the other layers' blocks hold different values."""
    b, kv, g, dh, s = 3, 2, 2, 32, 60
    q = _mk((b, kv, g, dh, s, 16), seed=5)[0]
    kvs = [_mk((b, kv, g, dh, s, 16), seed=5 + i)[1:3]
           for i in range(N_LAYERS)]
    lengths = jnp.array([60, 13, 1], jnp.int32)
    kp, vp, bt = _stacked_pool(kvs, bs_blk=16, n_blocks=16)
    want = ref.flash_decode_ref(q, *kvs[layer], lengths)
    got = flash_decode_paged(q, kp, vp, bt, lengths, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_flash_decode_paged_int8(layer):
    b, kv, g, dh, s = 2, 2, 2, 32, 48
    q = _mk((b, kv, g, dh, s, 16), seed=7)[0]
    quant = [(_quant(k), _quant(v)) for k, v in (
        _mk((b, kv, g, dh, s, 16), seed=7 + i)[1:3]
        for i in range(N_LAYERS))]
    lengths = jnp.array([48, 29], jnp.int32)
    (kq, ks_), (vq, vs_) = quant[layer]
    want = ref.flash_decode_ref(q, kq, vq, lengths, ks_, vs_)
    kp, vp, bt = _stacked_pool([(k[0], v[0]) for k, v in quant],
                               bs_blk=16, n_blocks=8)
    ksp, vsp, _ = _stacked_pool([(k[1][..., None], v[1][..., None])
                                 for k, v in quant], bs_blk=16, n_blocks=8)
    got = flash_decode_paged(q, kp, vp, bt, lengths, layer,
                             ksp[..., 0], vsp[..., 0], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_flash_decode_paged_zero_length_rows(layer):
    """Inactive slots (length 0) must come back as exact zeros."""
    b, kv, g, dh, s = 2, 2, 2, 16, 32
    q = _mk((b, kv, g, dh, s, 16), seed=9)[0]
    kvs = [_mk((b, kv, g, dh, s, 16), seed=9 + i)[1:3]
           for i in range(N_LAYERS)]
    lengths = jnp.array([32, 0], jnp.int32)
    kp, vp, bt = _stacked_pool(kvs, bs_blk=16, n_blocks=8)
    got = np.asarray(flash_decode_paged(q, kp, vp, bt, lengths, layer,
                                        interpret=True))
    want = np.asarray(ref.flash_decode_ref(q, *kvs[layer], lengths))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    assert np.array_equal(got[1], np.zeros_like(got[1]))


def test_flash_decode_respects_length():
    """Tokens beyond `length` must not influence the output."""
    case = (1, 2, 2, 16, 128, 32)
    q, k, v, _ = _mk(case)
    lengths = jnp.array([64], jnp.int32)
    base = flash_decode(q, k, v, lengths, bs=32, interpret=True)
    k2 = k.at[:, 64:].set(999.0)        # poison the invalid region
    v2 = v.at[:, 64:].set(-999.0)
    poisoned = flash_decode(q, k2, v2, lengths, bs=32, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                               atol=1e-6)
