"""The main-path Pallas kernels and the engine's step compile for a TPU
v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for a *described* v5e chip (no chip attached) at StableLM-2-12B widths
— d_model 5120, d_ff 13824, 8 KV heads x 160, 16-token KV blocks, the
engine's 8 rows per matmul — and the paged attention kernel at the chat
cells' pool and block table, head widths 128 and 256 lanes. This is
what interpret-mode tests cannot see: block shapes the Mosaic tiling
refuses, layouts it cannot lower, kernels over the VMEM limit, and
copies the compiler adds around them.
The topology is described inside a module fixture, so only the worker
that runs this file loads the TPU compiler; everything built from it
lives in fixtures or tests too.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import numpy as np

from repro import configs
from repro.core.packed_model import (ExpertPackedStack, PackedLinear,
                                     _kernel_blocks, expert_matmul)
from repro.kernels import ops
from repro.kernels.ell import ell_matmul
from repro.kernels.flash_decode import flash_decode_paged
from repro.models import lm
from repro.serving import Engine, EngineConfig
from repro.serving.engine import pool_aliases

D_MODEL, D_FF = 5120, 13824
N_KV, GROUP, D_HEAD, BLOCK = 8, 4, 160, 16
ROWS = 8                                  # rows of a packed matmul
N_LAYERS = 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a program compiled for a described chip is written to the
    # persistent cache but can never be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # Mosaic, not XLA
    assert compiled.memory_analysis() is not None
    return compiled


def _operand_shapes_text(compiled):
    """The compiled module printed with each operand's shape, as the
    device trace names an op."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(opts)


# the chat cells: 32 slots x 160 blocks of 16 over 5 layers of 5,120
# blocks; head dim 128 (Mistral-NeMo) or 160 stored in 256 lanes
# (StableLM-2, the pool's lane padding)
CELL_ROWS, CELL_TABLE, CELL_BLOCKS = 32, 160, 5120


@pytest.mark.parametrize("quant,width", [(False, 256), (True, 256),
                                         (False, 128), (True, 128)],
                         ids=("bf16", "int8", "bf16-w128", "int8-w128"))
def test_flash_decode_paged_compiles(one_chip, quant, width):
    """The kernel at the chat cells' shapes reads one layer of the
    stacked pool in place: it fits VMEM (the compiler refuses a kernel
    whose scratch does not), and its op keeps the block table as its
    first operand, which is how the benchmark finds it in the trace."""
    kv_dt = jnp.int8 if quant else jnp.bfloat16
    pool = (N_LAYERS, CELL_BLOCKS, N_KV, BLOCK, width)
    shapes = [((CELL_ROWS, N_KV, GROUP, width), jnp.bfloat16),
              (pool, kv_dt), (pool, kv_dt),
              ((CELL_ROWS, CELL_TABLE), jnp.int32), ((CELL_ROWS,), jnp.int32),
              ((), jnp.int32)]
    if quant:
        shapes += [(pool[:-1], jnp.float32)] * 2
    compiled = _compile(lambda *a: flash_decode_paged(*a, interpret=False),
                        one_chip, *shapes)
    # the device trace names the kernel's op after the pallas_call
    assert re.search(r"^\s*(ROOT )?%flash_decode_paged(\.\d+)? = ",
                     compiled.as_text(), re.M)
    # the benchmark's pattern for the kernel (chipbench/metrics/paged_attn*)
    assert re.search(r"custom-call\(s32\[%d,%d\]" % (CELL_ROWS, CELL_TABLE),
                     _operand_shapes_text(compiled))
    # no layer's pool is sliced out of the stacked one to be read
    assert not re.search(r"= \S+\[%d,%d,%d,%d\]" % pool[1:],
                         compiled.as_text())


@pytest.mark.parametrize("arch,d_head", [("mistral_nemo_12b", 128),
                                          ("stablelm_12b", D_HEAD)],
                         ids=("mistral", "stablelm"))
def test_engine_step_updates_the_pool_in_place(one_chip, monkeypatch, arch,
                                               d_head):
    """The engine's C = 8 step at tiny widths (2 layers, each model's
    real head width: Mistral-NeMo's 128, and StableLM-2's 160 with its
    own block): the pool is donated, its buffers come back as outputs,
    and no copy, slice or update-slice in the program has the shape of
    one layer's pool or of the stacked one. 128 blocks, so that a pool
    whose last dim were not whole lanes would get a blocks-minor layout
    from the compiler, and copies to the kernel's layout and back."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)   # Mosaic kernels
    cfg = configs.get(arch, smoke=True).with_(n_layers=2, d_head=d_head)
    params = jax.eval_shape(lambda k: lm.init(cfg, k)[0],
                            jax.random.PRNGKey(0))
    r, c = 4, 8
    eng = Engine(cfg, params, EngineConfig(n_slots=r, n_blocks=128,
                                           block_size=BLOCK, max_len=128,
                                           prefill_chunk=c))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._step_args(np.zeros((r, c), np.int32), np.zeros((r,), np.int32),
                       np.zeros((r,), bool)))
    text = eng._step_fn(c).lower(*args).compile().as_text()
    n_pool = len(jax.tree.leaves(eng.paged))
    assert pool_aliases(text, n_pool) == list(range(n_pool))
    layer = eng.paged.k.shape[1:]
    for shape in (layer, eng.paged.k.shape):
        dims = ",".join(map(str, shape))
        moved = re.findall(
            r"= \w+\[%s\]\S* (?:copy|dynamic-slice|dynamic-update-slice)\("
            % dims, text)
        assert not moved, (shape, moved)


@pytest.mark.parametrize("d_in,d_out", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=("w_up", "w_down"))
def test_slab_nm_matmul_compiles(one_chip, d_in, d_out):
    """The 2:4 SLaB kernel with the blocks packed serving picks."""
    n, m = 2, 4
    planes = ((n, d_in // m, d_out), (d_in // 32, d_out))
    w = PackedLinear(jax.ShapeDtypeStruct(planes[0], jnp.bfloat16),
                     jax.ShapeDtypeStruct(planes[0], jnp.int8),
                     jax.ShapeDtypeStruct(planes[1], jnp.uint32),
                     jax.ShapeDtypeStruct((d_out, 1), jnp.bfloat16),
                     jax.ShapeDtypeStruct((d_in, 1), jnp.bfloat16),
                     variant="slab-nm", m_pat=m, d_in=d_in, d_out=d_out,
                     rank=1)
    bn, bk = _kernel_blocks(w)
    assert bn % 128 == 0 and bk % 256 == 0, (bn, bk)
    _compile(lambda x, vals, idx, bp, u, v: ops.slab_nm_matmul(
        x, vals, idx, m, bp, u, v, bm=128, bn=bn, bk=bk, interpret=False),
        one_chip, ((ROWS, d_in), jnp.bfloat16),
        (planes[0], jnp.bfloat16), (planes[0], jnp.int8),
        (planes[1], jnp.uint32), ((d_out, 1), jnp.bfloat16),
        ((d_in, 1), jnp.bfloat16))


def test_expert_matmul_slab_nm_compiles(one_chip):
    """The expert path is the 2:4 SLaB kernel under ``jax.vmap``: at
    8 experts of ``ROWS`` tokens each over (D_MODEL, D_FF) planes it is
    ONE kernel, with the expert index added to its grid, reading x and
    the stacked planes in place. The only copies are the rank factors'
    (E, D, 1) -> (E, 1, D) transposes into the kernel's row stacks."""
    n, m, e = 2, 4, 8
    planes = ((e, n, D_MODEL // m, D_FF), (e, D_MODEL // 32, D_FF))

    def f(x, vals, idx, bp, u, v):
        w = PackedLinear(vals, idx, bp, u, v, variant="slab-nm", m_pat=m,
                         d_in=D_MODEL, d_out=D_FF, rank=1)
        eps = ExpertPackedStack((w,), None, (tuple(range(e)),), (), e)
        return expert_matmul(x, eps, interpret=False)

    text = _compile(f, one_chip, ((e, ROWS, D_MODEL), jnp.bfloat16),
                    (planes[0], jnp.bfloat16), (planes[0], jnp.int8),
                    (planes[1], jnp.uint32), ((e, D_FF, 1), jnp.bfloat16),
                    ((e, D_MODEL, 1), jnp.bfloat16)).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    copied = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert set(copied) <= {f"bf16[{e},1,{D_FF}]", f"bf16[{e},1,{D_MODEL}]"}, \
        copied


@pytest.mark.xfail(strict=True, reason=(
    "ELL does not lower to Mosaic: the fori_loop over K_max chunks is an "
    "unimplemented dynamic_slice, and static chunks make the 3-D "
    "jnp.take gather a shape mismatch"))
def test_ell_matmul_does_not_lower(one_chip):
    k_max = D_MODEL // 2                  # 50% unstructured rows
    _compile(lambda x, vals, idx: ell_matmul(x, vals, idx, bm=ROWS,
                                             bn=256, interpret=False),
             one_chip, ((ROWS, D_MODEL), jnp.bfloat16),
             ((D_FF, k_max), jnp.bfloat16), ((D_FF, k_max), jnp.uint16))
