"""Quickstart: decompose one weight matrix with SLaB and inspect every
piece of the paper's Eq. (1): W ≈ W_S + W_L ⊙ W_B.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import scores
from repro.core.apply import slab_linear
from repro.core.packed_model import pack_linear, packed_matmul
from repro.core.slab import (SLaBConfig, compression_ratio, keep_fraction,
                             reconstruct, slab_decompose)

# A fake "linear layer" weight and its calibration activations.
d_out, d_in = 512, 1024
w = jax.random.normal(jax.random.PRNGKey(0), (d_out, d_in)) * 0.02
x_cal = jax.random.normal(jax.random.PRNGKey(1), (256, d_in))
act_norms = scores.act_col_norms(x_cal)          # ‖X_j‖₂ (Wanda stats)

# --- decompose at 50% compression (paper's headline setting) ----------
cfg = SLaBConfig(cr=0.5, bits=16, iters=20)
dec = slab_decompose(w, act_norms, cfg)

print(f"keep fraction (Eq. 10): {keep_fraction(0.5, 16, d_out, d_in):.4f}")
print(f"nnz(W_S)/total:         {float(jnp.mean(dec.w_s != 0)):.4f}")
print(f"achieved CR (Eq. 9):    {compression_ratio(dec):.4f}")
print(f"W_B values:             {jnp.unique(dec.w_b)}")
print(f"W_L factors >= 0:       u {bool(jnp.all(dec.u >= 0))}, "
      f"v {bool(jnp.all(dec.v >= 0))}   (Prop. 2)")

err = float(jnp.linalg.norm(w - reconstruct(dec)) / jnp.linalg.norm(w))
print(f"relative recon error:   {err:.4f}")

# --- vs pruning alone at the same storage budget ----------------------
from repro.core import baselines
w_wanda = baselines.wanda_prune(w, act_norms, 0.5)
err_w = float(jnp.linalg.norm(w - w_wanda) / jnp.linalg.norm(w))
print(f"wanda@same budget:      {err_w:.4f}  "
      f"(SLaB recovers {100 * (1 - err / err_w):.1f}% of its error)")

# --- the same decomposition through the compressor registry -----------
# (core.compressor is the pluggable API the compression pipeline uses;
#  plans route each linear to a registered compressor by name)
from repro.core import compressor
print(f"registered compressors: {compressor.available()}")
slab_c = compressor.get("slab", cfg)
cl = slab_c.compress(w, compressor.LinearStats(norms=act_norms))
print(f"registry slab:          measured CR {cl.cr:.4f}, "
      f"dense-equivalent matches: "
      f"{bool(jnp.allclose(cl.dense, reconstruct(dec)))}")

# --- serve it ----------------------------------------------------------
x = jax.random.normal(jax.random.PRNGKey(2), (8, d_in))
y_ref = x @ reconstruct(dec).T
y_jnp = slab_linear(x, dec)                          # XLA path
pk = pack_linear(dec, None)                          # packed serving form
y_kern = packed_matmul(x, pk, interpret=True)        # Pallas kernel
print(f"XLA path max err:       {float(jnp.max(jnp.abs(y_jnp - y_ref))):.2e}")
print(f"Pallas kernel max err:  {float(jnp.max(jnp.abs(y_kern - y_ref))):.2e}")
print(f"packed variant:         {pk.variant}")
print(f"packed B matrix:        {pk.b_packed.shape} uint32 "
      f"(16x smaller than bf16)")
