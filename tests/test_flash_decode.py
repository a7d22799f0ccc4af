"""Flash-decode Pallas kernel: shape/dtype/length sweeps vs oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import (blocks_per_step, flash_decode,
                                        flash_decode_paged)

CASES = [  # (B, KV, G, dh, S, bs)
    (2, 4, 3, 32, 256, 64),
    (1, 8, 4, 64, 512, 128),
    (4, 2, 12, 64, 128, 128),    # qwen2-vl-like grouping, single chunk
    (2, 1, 1, 128, 256, 64),     # MQA
    (2, 2, 2, 64, 1024, 512),    # the default 512-token blocks: one a step
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


def _lanes(t, w):
    """Zero-pad the head dim to w lanes, as the paged cache's pool is."""
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, w - t.shape[-1]),))


# (layer, kv, dh, w, bs, s, lengths): two query heads per KV head. The
# first three are the pool's layers at P = 4 (s = 60, one group); with
# 32-token blocks P = 8 (256 tokens a group) and the 19-block table is
# not a multiple of it, nor is 16-token blocks' 19 one of P = 16.
PAGED_CASES = {
    "0": (0, 2, 32, 32, 16, 60, [60, 13, 1]),
    "1": (1, 2, 32, 32, 16, 60, [60, 13, 1]),
    "2": (2, 2, 32, 32, 16, 60, [60, 13, 1]),
    # mid-group, at a group's end (P*bs) and one past it
    "groups": (1, 2, 32, 32, 32, 600, [100, 256, 257]),
    # one KV head, as a shard of a sharded pool holds; a full table
    "kv1": (2, 1, 32, 32, 32, 600, [600, 32, 511]),
    # head dim 160 in 256 lanes (zeros past it), P = 16
    "w256-dh160": (0, 2, 160, 256, 16, 300, [300, 256, 17]),
}


@pytest.mark.parametrize("case", PAGED_CASES.values(), ids=PAGED_CASES)
def test_flash_decode_paged_matches_ref(case):
    """Each layer of a stacked pool matches the reference on its own
    K/V; the other layers' blocks hold different values."""
    layer, kv, dh, w, bs, s, lens = case
    b, g = len(lens), 2
    q = _mk((b, kv, g, dh, s, bs), seed=5)[0]
    kvs = [_mk((b, kv, g, dh, s, bs), seed=5 + i)[1:3]
           for i in range(N_LAYERS)]
    lengths = jnp.array(lens, jnp.int32)
    n_blocks = max(16, b * -(-s // bs))
    kp, vp, bt = _stacked_pool([(_lanes(k, w), _lanes(v, w)) for k, v in kvs],
                               bs_blk=bs, n_blocks=n_blocks)
    want = ref.flash_decode_ref(q, *kvs[layer], lengths)
    got = flash_decode_paged(_lanes(q, w), kp, vp, bt, lengths, layer,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got[..., :dh]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got[..., dh:]))     # zeros past the head


# (layer, kv, bs, s, lengths); the first two are the pool's layers at one
# group; "kv1-groups": one KV head (16 scale lanes of 128), P = 8, a
# length at a group's end and one past the next group's start
PAGED_INT8_CASES = {
    "0": (0, 2, 16, 48, [48, 29]),
    "1": (1, 2, 16, 48, [48, 29]),
    "2": (2, 2, 16, 48, [48, 29]),
    "kv1-groups": (1, 1, 32, 600, [256, 599]),
}


@pytest.mark.parametrize("case", PAGED_INT8_CASES.values(),
                         ids=PAGED_INT8_CASES)
def test_flash_decode_paged_int8(case):
    layer, kv, bs, s, lens = case
    b, g, dh = len(lens), 2, 32
    q = _mk((b, kv, g, dh, s, bs), seed=7)[0]
    quant = [(_quant(k), _quant(v)) for k, v in (
        _mk((b, kv, g, dh, s, bs), seed=7 + i)[1:3]
        for i in range(N_LAYERS))]
    lengths = jnp.array(lens, jnp.int32)
    (kq, ks_), (vq, vs_) = quant[layer]
    want = ref.flash_decode_ref(q, kq, vq, lengths, ks_, vs_)
    n_blocks = max(8, b * -(-s // bs))
    kp, vp, bt = _stacked_pool([(k[0], v[0]) for k, v in quant],
                               bs_blk=bs, n_blocks=n_blocks)
    ksp, vsp, _ = _stacked_pool([(k[1][..., None], v[1][..., None])
                                 for k, v in quant], bs_blk=bs,
                                n_blocks=n_blocks)
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


@pytest.mark.parametrize("quant", [False, True], ids=("f32", "int8"))
def test_flash_decode_paged_nothing_past_length(quant):
    """Every block that is not among a row's first ceil(length / bs),
    and every token past a row's length, holds NaN (scales too): the
    output stays finite and matches the reference, so nothing past the
    length reaches it. The table's entries past each length name
    poisoned blocks."""
    b, kv, g, dh, s, bs = 3, 2, 2, 32, 600, 32       # P = 8, 19 blocks
    q, k, v, _ = _mk((b, kv, g, dh, s, bs), seed=11)
    lengths = np.array([300, 0, 257], np.int32)
    scales = (None, None)
    if quant:
        (k, k_s), (v, v_s) = _quant(k), _quant(v)
        want = ref.flash_decode_ref(q, k, v, jnp.asarray(lengths), k_s, v_s)
        ksp, vsp, _ = _scatter_to_pool(k_s[..., None], v_s[..., None], bs,
                                       64)
        scales = (ksp[None, ..., 0], vsp[None, ..., 0])
    else:
        want = ref.flash_decode_ref(q, k, v, jnp.asarray(lengths))
    kp, vp, bt = _scatter_to_pool(k, v, bs, 64)
    bt = np.asarray(bt)
    live = np.zeros(64, bool)
    for r, n in enumerate(lengths):
        live[bt[r, :-(-n // bs)]] = True
    tok = np.arange(bs)

    def poison(pool, dt):
        pool = np.asarray(pool, np.float32).copy()
        pool[~live] = np.nan
        for r, n in enumerate(lengths):
            if n % bs:                  # the tokens past n in its block
                pool[bt[r, n // bs]][..., tok >= n % bs, :] = np.nan
        return jnp.asarray(pool, dt)

    if quant:           # int8 holds no NaN: poison the scales instead
        kp, vp = kp[None], vp[None]
        scales = tuple(poison(t[0][..., None], jnp.float32)[None, ..., 0]
                       for t in scales)
    else:
        kp, vp = poison(kp, kp.dtype)[None], poison(vp, vp.dtype)[None]
    got = np.asarray(flash_decode_paged(q, kp, vp, jnp.asarray(bt),
                                        jnp.asarray(lengths), 0, *scales,
                                        interpret=True))
    assert np.all(np.isfinite(got))
    live_rows = lengths > 0
    np.testing.assert_allclose(got[live_rows], np.asarray(want)[live_rows],
                               rtol=1e-5, atol=1e-5)
    assert not np.any(got[~live_rows])


@pytest.mark.parametrize("shape,want", [
    ((8, 16, 128, 2, 160), 16),        # Mistral-NeMo's chat pool, bf16
    ((8, 16, 256, 2, 160), 16),        # StableLM-2's, 160 in 256 lanes
    ((8, 512, 128, 2, 4), 1),          # flash_decode's 512-token blocks
    ((2, 16, 128, 1, 5), 5),           # a table shorter than a group
], ids=("mistral", "stablelm", "contiguous", "short-table"))
def test_blocks_per_step(shape, want):
    assert blocks_per_step(*shape) == want


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
