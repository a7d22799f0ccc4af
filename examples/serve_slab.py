"""Serve a SLaB-compressed model with batched requests.

    PYTHONPATH=src python examples/serve_slab.py

Flow: init model -> layer-wise SLaB compression (calibrated) -> batched
greedy decoding with KV cache; reports tokens/s and the weight-stream
byte reduction the compressed format gives a memory-bound decoder.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.packed_model import pack_linear
from repro.core.pipeline import compress_model, linear_paths
from repro.core.slab import SLaBConfig, slab_decompose
from repro.data import SyntheticCorpus, calibration_batch
from repro.launch.serve import greedy_decode
from repro.models import lm


def main():
    cfg = configs.get("llama2_7b", smoke=True)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    print(f"{cfg.name}: {lm.param_count(cfg)/1e6:.2f}M params")

    cal = calibration_batch(cfg.vocab, n_seq=8, seq_len=64)
    t0 = time.monotonic()
    # plan API: one catch-all rule (equivalent to method="slab" sugar)
    params_c, stats = compress_model(cfg, params, cal,
                                     plan="*=slab@cr=0.5,iters=8")
    cr_meas = float(np.mean([s.cr for s in stats]))
    print(f"compressed {len(stats)} linears (measured CR={cr_meas:.3f}) "
          f"in {time.monotonic()-t0:.1f}s")

    # storage accounting on one layer's wq
    w = params["layers"]["attn"]["wq"][0].T.astype(jnp.float32)
    dec = slab_decompose(w, None, SLaBConfig(cr=0.5, iters=8))
    pk = pack_linear(dec, None)
    dense_bytes = w.size * 2
    nnz = int(jnp.sum(dec.w_s != 0))
    packed_bytes = nnz * 2 + pk.b_packed.size * 4 + (pk.u.size + pk.v.size) * 2
    print(f"weight stream: dense {dense_bytes}B -> SLaB-packed "
          f"{packed_bytes}B ({dense_bytes/packed_bytes:.2f}x less HBM "
          f"traffic per decode step)")

    corpus = SyntheticCorpus(cfg.vocab, seed=0)
    b, s_in, s_out = 8, 32, 16
    prompts = jnp.asarray(corpus.batch(0, b, s_in)["inputs"])
    t0 = time.monotonic()
    gen = greedy_decode(cfg, params_c, prompts, s_out)
    dt = time.monotonic() - t0
    print(f"served batch={b}: {(s_in+s_out)*b/dt:.1f} tok/s "
          f"(CPU, uncompiled-cache timing)")

    # quality spot check: compressed model still prefers corpus structure
    logits, _ = lm.forward(cfg.with_(dtype=jnp.float32),
                           jax.tree.map(lambda x: x.astype(jnp.float32),
                                        params_c),
                           prompts)
    acc = float(jnp.mean(jnp.argmax(logits[:, :-1], -1) ==
                         prompts[:, 1:]))
    print(f"next-token agreement on prompts: {100*acc:.1f}%")


if __name__ == "__main__":
    main()
