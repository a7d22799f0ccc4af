"""Kernel micro-benchmarks: fused SLaB linear vs dense matmul vs naive
two-matmul decomposition, plus packed-format HBM-byte accounting.

On CPU the interpret-mode timings are NOT TPU-representative — the
meaningful outputs here are (a) correctness at bench shapes and (b) the
bytes-streamed table (the roofline input for the decode hillclimb):

  dense bf16:             16 bits/weight
  SLaB unstructured:      16·keep + 1 (bits) + rank-1 vectors
  SLaB 2:4 packed:        8·16/16 + 2 idx + 1  ≈ 11 bits/weight at 50% CR
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing, slab
from repro.core.packed_model import pack_linear, packed_matmul
from repro.core.slab import SLaBConfig
from benchmarks.common import emit

SHAPES = [(512, 2048, 2048), (256, 4096, 4096)]


def weight_stream_bits(dec, pattern):
    d_out, d_in = dec.w_s.shape
    total = d_out * d_in
    if pattern:
        pk = packing.pack_nm(dec.w_s, *map(int, pattern.split(":")))
        sparse_bits = packing.nm_packed_bits(pk, bits=16)
    else:
        nnz = int(jnp.sum(dec.w_s != 0))
        sparse_bits = nnz * 16 + nnz * int(np.ceil(np.log2(d_in)))  # ELL
    bits = sparse_bits + total + 16 * (d_out + d_in)   # + W_B + u,v
    return bits / total


def run():
    rows = []
    for m, n, k in SHAPES:
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (n, k),
                              jnp.float32) * 0.05
        for pattern in (None, "2:4"):
            dec = slab.slab_decompose(
                w, None, SLaBConfig(cr=0.5, iters=2, pattern=pattern))
            pk = pack_linear(dec, pattern)
            got = packed_matmul(x, pk, interpret=True)
            want = x @ slab.reconstruct(dec).T
            err = float(jnp.max(jnp.abs(got - want)))
            bits = weight_stream_bits(dec, pattern)
            rows.append({
                "shape": f"{m}x{n}x{k}",
                "pattern": pattern or "unstructured",
                "variant": pk.variant,
                "max_err_vs_dense_reconstruction": err,
                "bits_per_weight_streamed": round(bits, 2),
                "dense_bits": 16,
                "hbm_reduction": round(16 / bits, 2),
            })
            print(rows[-1], flush=True)
    emit("kernels", rows)
    return rows


if __name__ == "__main__":
    run()
