"""SLaB linears, W = W_S + (u vᵀ) ⊙ W_B, with W_S exactly N:M sparse,
packed by the program's own ``pack_plan_decs`` into its ``slab-nm``
serving format.

The decomposition is drawn from the seed, not computed from dense
weights: calibrate-and-compress is the offline job, not serving. Its
values are bf16 from the start, so packing them in bf16 loses nothing
and the reference multiplies by exactly what the program stores.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights


def _nm_mask(key, d_out: int, d_in: int, n: int, m: int) -> jax.Array:
    """Keep n of every m consecutive inputs, chosen uniformly: the n
    smallest of m uniform scores per group (ties broken by position)."""
    s = jax.random.uniform(key, (d_out, d_in // m, m))
    pos = jnp.arange(m)
    before = ((s[..., None, :] < s[..., :, None])
              | ((s[..., None, :] == s[..., :, None])
                 & (pos[None, :] < pos[:, None])))
    rank = jnp.sum(before, axis=-1)
    return (rank < n).reshape(d_out, d_in)


def _decomposition(key, d_in: int, d_out: int, n: int, m: int, rank: int):
    """One linear in paper orientation (d_out, d_in): W_S uniform on its
    kept entries, W_B uniform signs, u and v positive. W_S and the
    rank-r binary term each carry half of the variance 1 / d_in."""
    ks, km, kb, ku, kv = jax.random.split(key, 5)
    a = (3.0 / d_in) ** 0.5
    vals = weights.uniform(ks, (d_out, d_in), -a, a, jnp.bfloat16)
    w_s = jnp.where(_nm_mask(km, d_out, d_in, n, m), vals,
                    jnp.zeros((), jnp.bfloat16))
    w_b = jnp.where(jax.random.bernoulli(kb, 0.5, (d_out, d_in)),
                    1, -1).astype(jnp.int8)
    # E[(u v)^2] = c^4 (13/12)^2 over the r terms = 0.5 / d_in
    c = (0.5 / (rank * d_in)) ** 0.25 / (13.0 / 12.0) ** 0.5
    u = weights.uniform(ku, (d_out, rank), 0.5 * c, 1.5 * c, jnp.bfloat16)
    v = weights.uniform(kv, (d_in, rank), 0.5 * c, 1.5 * c, jnp.bfloat16)
    return {"w_s": w_s, "w_b": w_b, "u": u, "v": v}


@functools.partial(jax.jit, static_argnames=("shapes", "n", "m", "rank"))
def _make(key, shapes, n, m, rank):
    return {path: _decomposition(jax.random.fold_in(key, i), d_in, d_out,
                                 n, m, rank)
            for i, (path, (d_in, d_out)) in enumerate(shapes)}


def make_layer(key, shapes: weights.Shapes, fmt: dict) -> Dict:
    n, m = (int(x) for x in fmt["pattern"].split(":"))
    return _make(key, tuple(sorted(shapes.items())), n, m,
                 int(fmt["rank"]))


def program_linears(make: Callable[[int], Dict], n_layers: int, fmt: dict,
                    dtype=jnp.bfloat16) -> Dict:
    """Pack each layer with ``pack_plan_decs`` under the plan ``*=slab``
    at the format's pattern and CR, then stack the layers. Every linear
    must come out ``slab-nm`` with no dense fallback. The dense linears
    are never made: the packer sees stand-ins of their shape and dtype
    that hold no memory."""
    from repro.core.packed_model import pack_plan_decs
    from repro.core.plan import CompressionPlan
    from repro.core.slab import SLaBConfig, SLaBDecomposition
    plan = CompressionPlan.parse(
        "*=slab", base=SLaBConfig(cr=float(fmt["cr"]),
                                  pattern=fmt["pattern"],
                                  rank=int(fmt["rank"])))
    per_layer = []
    for l in range(n_layers):
        parts = make(l)
        decs = {(0, p): SLaBDecomposition(d["w_s"], d["u"], d["v"],
                                          d["w_b"])
                for p, d in parts.items()}
        stand_in: dict = {}
        for p, d in parts.items():
            d_out, d_in = d["w_s"].shape
            grp, leaf = p.split(".")
            stand_in.setdefault(grp, {})[leaf] = np.broadcast_to(
                np.zeros((), dtype), (1, d_in, d_out))
        packed, report = pack_plan_decs(
            {"layers": stand_in}, decs, 1, plan, dtype=dtype,
            variants={k: "slab-nm" for k in decs})
        if dict(report.by_variant) != {"slab-nm": len(decs)} \
                or report.fallback:
            raise RuntimeError(
                f"layer {l}: packed {dict(report.by_variant)}, dense "
                f"fallback {report.fallback}; want every linear slab-nm")
        per_layer.append({p: packed["layers"][p.split(".")[0]][
            p.split(".")[1]] for p in parts})
        del parts, decs, packed
    out = {}
    for p in list(per_layer[0]):
        leaves = [layer.pop(p) for layer in per_layer]
        out[p] = jax.tree.map(lambda *xs: jnp.concatenate(xs), *leaves)
        del leaves
    return out


def dense_equivalent(part) -> jax.Array:
    """(W_S + (u vᵀ) ⊙ W_B)ᵀ in float32: the (d_in, d_out) matrix."""
    lr = jnp.matmul(part["u"].astype(jnp.float32),
                    part["v"].astype(jnp.float32).T,
                    precision=jax.lax.Precision.HIGHEST)
    w = part["w_s"].astype(jnp.float32) + lr * part["w_b"].astype(
        jnp.float32)
    return w.T
