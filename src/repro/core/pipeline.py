"""Layer-wise one-shot compression driver (the SparseGPT/Wanda protocol
the paper follows, §II-A1), with calibration statistics sourced from
**activation taps** and per-linear policy from a **CompressionPlan**.

  for each transformer layer, in order:
    (1) forward the calibration set — streamed in CalibrationSpec
        chunks — through the *already-compressed* prefix to the layer's
        inputs,
    (2) run the layer's REAL forward (``models.lm._layer_fwd``) under
        one ``models.common.tap_capture``: the ``linear()`` dispatch
        chokepoint reports every linear's exact input, reduced on the
        fly to ‖X‖₂ column norms and — only for linears whose resolved
        compressor declares ``"hessian" in needs`` — X^T X Hessians,
        accumulated across all calibration chunks,
    (3) resolve every linear through the plan (ordered glob rules over
        layer index + ``linear_paths`` names) and compress it with the
        matched registry compressor at the rule's config,
    (4) replace the weights and continue forward with the compressed
        layer's outputs (error propagation).

The tap protocol: modules name their linears (``linear(x, w,
tap="wq")``) under scope prefixes pushed by the layer assembly
("attn", "mlp", "moe", "moe.shared", "mamba", "shared"), so tap names
equal the ``linear_paths`` / ``shared_linear_paths`` entries below by
construction. One source of truth — attention, MoE dispatch (per-expert
stats see exactly the dispatched-token subsets, capacity drops
included), the Mamba-2 SSD scan, and the hybrid shared block are never
re-derived here. New scoring variants plug in through
``core.compressor.register`` + a plan rule, with zero edits to this
file.

Works on the model zoo's stacked-params layout: weights live as
``params["layers"][...]`` leaves with a leading L dim; we slice layer l,
compress its 2-D linears, and write them back. MoE experts are
compressed per-expert with expert-specific activation statistics. The
hybrid (zamba2) *shared* transformer block lives outside the stack
(``params["shared_attn"]``) and is compressed once, at its first firing
layer, from that invocation's ``shared.*`` taps — later invocations
then run (and propagate error through) the compressed shared weights.

Per the paper, embeddings and the LM head are excluded (§III-A4); norms,
biases and other 1-D leaves are untouched.

Stat collection and compression are **separable stages**:
``collect_model_stats`` runs ONE streaming calibration pass over the
uncompressed model and returns every layer's tapped statistics as a
``ModelTapStats``; ``compress_model(..., stats=...)`` then compresses
from those precollected statistics without any further forwards. The
sensitivity-driven budget allocator (``core.allocator``) is built on
this split — it probes per-layer CR→error frontiers from one pass and
hands both the concrete plan and the same stats back to the
compression stage, so allocate+compress costs exactly one calibration
pass. A plan with unallocated ``@auto`` rules routes through the
allocator automatically. (The classic single-call path keeps the
paper's error-propagation protocol: stats are tapped per layer from
the already-compressed prefix.)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.core import scores as scores_lib
from repro.core.compressor import LinearStats
from repro.core.slab import SLaBConfig
from repro.models import lm
from repro.models.common import ArchConfig, positions_for, tap_capture

Array = jax.Array


@dataclasses.dataclass
class CompressStats:
    layer: int
    name: str
    err_before: float   # ‖W diag(n)‖_F — the zero-approximation baseline
    err_after: float    # ‖(W - Ŵ) diag(n)‖_F with the same tapped norms
    cr: float           # measured compression ratio (requested if unknown)
    method: str = ""
    variant: str = ""   # packed-serving variant (core.packed_model
                        # variant_of); "" = no kernel-servable form
    cr_requested: float = 0.0   # the CR the resolved plan rule asked for
                                # (allocator decisions stay observable
                                # next to the measured value)


@dataclasses.dataclass
class ModelTapStats:
    """Whole-model tap statistics from ONE streaming calibration pass.

    Keys are ``(layer, path)`` with ``path`` a ``linear_paths`` /
    ``shared_linear_paths`` name (shared.* entries appear at the shared
    block's first firing layer, matching where the pipeline compresses
    them). ``n_forwards`` counts the ``models.lm._layer_fwd``
    invocations consumed — ``n_layers * n_chunks`` for one pass."""

    norms: Dict[Tuple[int, str], Array]
    hessians: Dict[Tuple[int, str], Array]
    n_forwards: int = 0


def _get(d: dict, path: str):
    cur = d
    for k in path.split("."):
        if k not in cur:
            return None
        cur = cur[k]
    return cur


def _set(d: dict, path: str, val):
    ks = path.split(".")
    cur = d
    for k in ks[:-1]:
        cur = cur[k]
    cur[ks[-1]] = val


def linear_paths(cfg: ArchConfig) -> List[str]:
    """Compressible 2-D linears inside one layer of this family."""
    if cfg.family in ("ssm", "hybrid"):
        return ["mamba.in_z", "mamba.in_x", "mamba.out"]
    paths = ["attn.wq", "attn.wk", "attn.wv", "attn.wo"]
    if cfg.family == "moe":
        paths += ["moe.w_gate", "moe.w_up", "moe.w_down"]  # (E, D, F) 3-D
        if cfg.shared_ff:
            paths += ["moe.shared.w_gate", "moe.shared.w_up",
                      "moe.shared.w_down"]
    elif cfg.act == "swiglu":
        paths += ["mlp.w_gate", "mlp.w_up", "mlp.w_down"]
    else:
        paths += ["mlp.w_up", "mlp.w_down"]
    return paths


def shared_linear_paths(cfg: ArchConfig) -> List[str]:
    """Hybrid (zamba2) shared-transformer-block linears. They live in
    ``params["shared_attn"]`` (outside the stacked layers) and tap as
    ``shared.*`` at layers where the block fires."""
    if cfg.family != "hybrid" or not cfg.attn_every:
        return []
    # the shared block is a plain attn+mlp transformer block: reuse the
    # dense-family path list under the "shared." tap scope
    return ["shared." + p for p in linear_paths(cfg.with_(family="dense"))]


def _capture_layer(cfg: ArchConfig, params: dict, lp: dict, idx: int,
                   chunks, positions: Sequence[Array],
                   paths: Sequence[str], hessian_names: set,
                   propagate: bool = False
                   ) -> Tuple[Dict[str, Array], Dict[str, Array]]:
    """Run layer ``idx``'s real forward over every calibration chunk
    under ONE activation-tap capture: statistics accumulate across
    chunks (streaming multi-batch calibration). ``propagate`` writes
    each chunk's output back into ``chunks`` (the uncompressed-model
    stats pass, where the capture forward doubles as propagation)."""
    with tap_capture(hessian=bool(hessian_names),
                     hessian_names=set(hessian_names)) as tap:
        for i in range(len(chunks)):
            out, _ = lm._layer_fwd(cfg, params, lp, jnp.asarray(idx),
                                   chunks[i], positions[i])
            if propagate:
                chunks[i] = out
    acts: Dict[str, Array] = {}
    hess: Dict[str, Array] = {}
    for pth in paths:
        if not tap.has(pth):
            continue
        acts[pth] = tap.norms(pth)
        hz = tap.hessian(pth)
        if hz is not None:
            hess[pth] = hz
    return acts, hess


def layer_tap_stats(cfg: ArchConfig, params: dict, lp: dict, idx: int,
                    h: Array, positions: Array, hessian: bool = False,
                    hessian_names: Optional[set] = None
                    ) -> Tuple[Dict[str, Array], Dict[str, Array]]:
    """Single-batch convenience wrapper around ``_capture_layer``.

    Returns ``(act_norms, hessians)`` keyed by ``linear_paths`` /
    ``shared_linear_paths`` names: norms are (D_in,) — stacked (E, D_in)
    for MoE experts — and Hessians X^T X are (D_in, D_in) /
    (E, D_in, D_in); ``hessians`` is empty unless requested.
    """
    paths = linear_paths(cfg) + shared_linear_paths(cfg)
    names = set(paths) if hessian and hessian_names is None \
        else set(hessian_names or ())
    return _capture_layer(cfg, params, lp, idx, [h], [positions],
                          paths, names)


def collect_model_stats(cfg: ArchConfig, params: dict, calib,
                        plan=None,
                        hessian_names=None,
                        progress: Optional[Callable[[str], None]] = None
                        ) -> ModelTapStats:
    """ONE streaming calibration pass over the *uncompressed* model,
    tapping every layer's statistics (the allocator's sensitivity probe
    and the input to ``compress_model(stats=...)``).

    Each layer's capture forward doubles as the propagation to the next
    layer (weights are unchanged), so the whole collection costs exactly
    ``n_layers * n_chunks`` ``_layer_fwd`` calls — one pass. Hessians
    (X^T X) are accumulated for linears whose plan-resolved compressor
    declares ``"hessian" in needs`` (``@auto`` rules are probed at the
    base config); ``hessian_names`` overrides (a set of path names, or
    True for all)."""
    if plan is not None:
        plan = plan_lib.CompressionPlan.parse(plan)
    spec = (calib if isinstance(calib, plan_lib.CalibrationSpec)
            else plan_lib.CalibrationSpec(np.asarray(calib)))
    chunks: List[Array] = []
    positions: List[Array] = []
    for t in spec.batches():
        h = lm.embed_inputs(cfg, params, jnp.asarray(t))
        chunks.append(h)
        positions.append(positions_for(cfg, h.shape[0], h.shape[1]))

    norms: Dict[Tuple[int, str], Array] = {}
    hessians: Dict[Tuple[int, str], Array] = {}
    n_fwd = 0
    shared_pending = bool(cfg.family == "hybrid" and cfg.attn_every
                          and "shared_attn" in params)
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        shared_now = (shared_pending
                      and l % cfg.attn_every == cfg.attn_every - 1)
        tap_paths = linear_paths(cfg) + (shared_linear_paths(cfg)
                                         if shared_now else [])
        if hessian_names is True:
            hnames = set(tap_paths)
        elif hessian_names is not None:
            hnames = set(hessian_names) & set(tap_paths)
        elif plan is not None:
            hnames = set()
            for p in tap_paths:
                r = plan.resolve(l, p, allow_auto=True)
                if r is not None and "hessian" in r.needs:
                    hnames.add(p)
        else:
            hnames = set()
        acts, hess = _capture_layer(cfg, params, lp, l, chunks, positions,
                                    tap_paths, hnames, propagate=True)
        n_fwd += len(chunks)
        for pth, an in acts.items():
            norms[(l, pth)] = an
        for pth, hz in hess.items():
            hessians[(l, pth)] = hz
        if shared_now:
            shared_pending = False
        if progress:
            progress(f"stats layer {l + 1}/{cfg.n_layers} tapped")
    return ModelTapStats(norms, hessians, n_fwd)


def _expert_hessians(hz: Optional[Array], n_exp: int, d_in: int
                     ) -> List[Optional[Array]]:
    """Per-expert Hessian slices. An expert that saw no calibration
    tokens (all-zero Gram) falls back to the identity, which reduces
    Hessian-aware methods to magnitude pruning instead of zeroing the
    expert. The zero-Gram check reads every expert's trace in a single
    device->host transfer."""
    if hz is None:
        return [None] * n_exp
    per = [hz[e] if hz.ndim == 3 else hz for e in range(n_exp)]
    tr = np.asarray(jnp.trace(hz, axis1=-2, axis2=-1)).reshape(-1)
    eye: Optional[Array] = None
    out: List[Optional[Array]] = []
    for e in range(n_exp):
        if tr[e if tr.size > 1 else 0] == 0.0:
            if eye is None:
                eye = jnp.eye(d_in, dtype=jnp.float32)
            out.append(eye)
        else:
            out.append(per[e])
    return out


def _weighted_errs(w: Array, w_new: Array, an: Optional[Array]
                   ) -> Tuple[float, float]:
    """(err_before, err_after): activation-weighted Frobenius error of
    the zero approximation (the pre-compression baseline — what a layer
    would lose if the linear were dropped entirely) and of the actual
    reconstruction, both under the same tapped norms."""
    wt = w.T.astype(jnp.float32)
    zero = jnp.zeros_like(wt)
    err_b = float(scores_lib.weighted_fro_error(wt, zero, an))
    err_a = float(scores_lib.weighted_fro_error(
        wt, w_new.T.astype(jnp.float32), an))
    return err_b, err_a


def _compress_leaf(layer: int, pth: str, w: Array, an: Optional[Array],
                   hz: Optional[Array],
                   r: plan_lib.ResolvedCompression):
    """Compress one parameter leaf (2-D linear or 3-D stacked experts).
    Returns (new weight, dec-or-None, CompressStats). Weights are stored
    (D_in, D_out) in our models — transposed to the paper's (D_out,
    D_in) convention for the compressor and back."""
    comp = r.compressor
    if w.ndim == 3:        # MoE experts (E, D, F): per-expert
        hz_e = _expert_hessians(hz, w.shape[0], w.shape[1])
        outs, crs, e_decs = [], [], []
        eb2 = ea2 = 0.0
        for e in range(w.shape[0]):
            an_e = an[e] if (an is not None and an.ndim == 2) else an
            cl = comp.compress(w[e].T.astype(jnp.float32),
                               LinearStats(norms=an_e, hessian=hz_e[e]))
            o = cl.dense.T.astype(w.dtype)
            outs.append(o)
            e_decs.append(cl.dec)
            if cl.cr is not None:
                crs.append(cl.cr)
            b_e, a_e = _weighted_errs(w[e], o, an_e)
            eb2 += b_e ** 2
            ea2 += a_e ** 2
        w_new = jnp.stack(outs)
        cr = float(np.mean(crs)) if crs else comp.scfg.cr
        # the per-expert decs travel as a tuple — pack_plan_decs routes
        # 3-D leaves to pack_expert_stack (expert-axis kernel launches)
        dec = tuple(e_decs) if all(d is not None for d in e_decs) else None
        st = CompressStats(layer, pth, float(np.sqrt(eb2)),
                           float(np.sqrt(ea2)), cr, r.method,
                           "expert" if dec is not None else "",
                           cr_requested=float(r.scfg.cr))
        return w_new, dec, st
    cl = comp.compress(w.T.astype(jnp.float32),
                       LinearStats(norms=an, hessian=hz))
    w_new = cl.dense.T.astype(w.dtype)
    err_b, err_a = _weighted_errs(w, w_new, an)
    cr = cl.cr if cl.cr is not None else comp.scfg.cr
    variant = ""
    dec = cl.dec
    if dec is not None:
        from repro.core.packed_model import variant_of
        if dec.w_s is not None and dec.w_s.dtype != w.dtype:
            # the sparse part is kept at the weight's own width: packing
            # serves it at that width anyway, and f32 copies of every
            # linear's W_S would double the memory held for packing
            dec = dec._replace(w_s=dec.w_s.astype(w.dtype))
        variant = variant_of(dec, r.scfg.pattern) or ""
    return w_new, dec, CompressStats(layer, pth, err_b, err_a, cr,
                                        r.method, variant,
                                        cr_requested=float(r.scfg.cr))


def compress_model(cfg: ArchConfig, params: dict, calib,
                   method: str = "slab",
                   scfg: SLaBConfig = SLaBConfig(),
                   plan=None,
                   collect_hessian: bool = False,
                   progress: Optional[Callable[[str], None]] = None,
                   keep_decompositions: bool = False,
                   stats: Optional[ModelTapStats] = None):
    """Run the layer-wise protocol. Returns (new params, stats[, decs]).

    ``calib`` is an (N, S) int32 array (or (N, S, D) embeds for
    stub-frontend families), or a ``plan.CalibrationSpec`` to stream it
    in chunks (tap statistics accumulate across chunks). ``plan`` is
    anything ``CompressionPlan.parse`` accepts (a plan, inline DSL,
    JSON, a rule list); when None, ``method``/``scfg`` act as sugar for
    a single catch-all rule. Hessians (X^T X) are tapped only for
    linears whose resolved compressor declares ``"hessian" in needs``
    (or when ``collect_hessian`` forces it). ``keep_decompositions``
    additionally returns {(layer, path): dec} for
    core.packed_model.pack_plan_decs (kernel-served packed weights;
    pruning-only methods contribute sparse-only decompositions).

    ``stats`` (a ``ModelTapStats`` from ``collect_model_stats``)
    compresses from precollected statistics instead: no calibration
    forwards run at all (``calib`` may be None) and error propagation
    is skipped — the statistics describe the uncompressed model. A plan
    with ``@auto`` rules is first routed through the budget allocator
    (``core.allocator.allocate_plan``), which itself collects ``stats``
    when not given — the whole allocate+compress flow then costs
    exactly one calibration pass."""
    plan = (plan_lib.CompressionPlan.parse(plan, base=scfg)
            if plan is not None else plan_lib.plan_for_method(method, scfg))
    if plan.wants_allocation:
        from repro.core import allocator as allocator_lib
        allocation = allocator_lib.allocate_plan(
            cfg, params, calib, plan=plan, stats=stats, progress=progress)
        plan, stats = allocation.plan, allocation.stats
    precollected = stats is not None

    out_stats: List[CompressStats] = []
    decs: Dict[Tuple[int, str], object] = {}
    params = dict(params)   # top-level copy: shared_attn swapped in place
    chunks: List[Array] = []
    positions: List[Array] = []
    if not precollected:
        if calib is None:
            raise ValueError("compress_model needs calibration data "
                             "(or precollected stats=)")
        spec = (calib if isinstance(calib, plan_lib.CalibrationSpec)
                else plan_lib.CalibrationSpec(np.asarray(calib)))
        for t in spec.batches():
            h = lm.embed_inputs(cfg, params, jnp.asarray(t))
            chunks.append(h)
            positions.append(positions_for(cfg, h.shape[0], h.shape[1]))
    new_layers = jax.tree.map(lambda a: a, params["layers"])  # shallow copy
    shared_pending = bool(cfg.family == "hybrid" and cfg.attn_every
                          and "shared_attn" in params)

    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        paths = linear_paths(cfg)
        shared_now = (shared_pending
                      and l % cfg.attn_every == cfg.attn_every - 1)
        tap_paths = paths + (shared_linear_paths(cfg) if shared_now else [])
        resolved = {p: plan.resolve(l, p) for p in tap_paths}
        if precollected:
            acts = {p: stats.norms[(l, p)] for p in tap_paths
                    if (l, p) in stats.norms}
            hess = {p: stats.hessians[(l, p)] for p in tap_paths
                    if (l, p) in stats.hessians}
        else:
            hess_names = {p for p, r in resolved.items()
                          if r is not None and "hessian" in r.needs}
            if collect_hessian:
                hess_names = set(tap_paths)
            acts, hess = _capture_layer(cfg, params, lp, l, chunks,
                                        positions, tap_paths, hess_names)

        for pth in paths:
            r = resolved[pth]
            w = _get(lp, pth)
            if r is None or w is None:
                continue
            w_new, dec, st = _compress_leaf(l, pth, w, acts.get(pth),
                                            hess.get(pth), r)
            if keep_decompositions and dec is not None:
                decs[(l, pth)] = dec
            out_stats.append(st)
            _set(lp, pth, w_new)

        if shared_now:
            sp = jax.tree.map(lambda a: a, params["shared_attn"])
            changed = False
            for pth in shared_linear_paths(cfg):
                r = resolved[pth]
                sub = pth.split(".", 1)[1]       # strip the "shared." scope
                w = _get(sp, sub)
                if r is None or w is None:
                    continue
                w_new, dec, st = _compress_leaf(l, pth, w, acts.get(pth),
                                                hess.get(pth), r)
                if keep_decompositions and dec is not None:
                    # keyed at the firing layer under the "shared." path;
                    # pack_plan_decs packs these into params["shared_attn"]
                    decs[(l, pth)] = dec
                out_stats.append(st)
                _set(sp, sub, w_new)
                changed = True
            if changed:
                params["shared_attn"] = sp
            shared_pending = False   # one-shot: first firing layer only

        # write back and propagate through the *compressed* layer
        new_layers = jax.tree.map(
            lambda buf, leaf: buf.at[l].set(leaf), new_layers, lp)
        for i in range(len(chunks)):
            chunks[i], _ = lm._layer_fwd(cfg, params, lp, jnp.asarray(l),
                                         chunks[i], positions[i])
        if progress:
            progress(f"layer {l + 1}/{cfg.n_layers} compressed")

    out = dict(params)
    out["layers"] = new_layers
    if keep_decompositions:
        return out, out_stats, decs
    return out, out_stats
