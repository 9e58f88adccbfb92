"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, so a kernel that Mosaic refuses
(tiling, VMEM) or a program that does not fit a chip fails here, on the
CPU, before it costs chip time. Nothing runs: these tests check that the
programs compile, that the paged kernel is in the compiled HLO as a
``tpu_custom_call``, and what the compiler says about memory.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.distributed.sharding import (TP, kv_pool_pspec,
                                        megastep_input_pspecs,
                                        megastep_output_pspec,
                                        serving_param_pspecs)
from repro.kernels.paged_attention.kernel import paged_prefill_attention_bcd
from repro.models import abstract_params, build
from repro.models import transformer as tr

# the serving shapes chip_smoke.py runs: --block-size 16 (one bf16 sublane
# tile per (blk, head_dim) slab) and --token-budget 256, whose widest
# bucket is C == 256; decode rows are C == 1
BLOCK_SIZE = 16
TOKEN_BUDGET = 256
MAX_BATCH, NUM_BLOCKS, MAX_LEN = 4, 1024, 3072
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _megastep_args(sharding, C):
    """Row inputs of one megastep at bucket width ``C``."""
    i32, B = jnp.int32, MAX_BATCH
    return (_sds(sharding, (B, C), i32), _sds(sharding, (B,), i32),
            _sds(sharding, (B,), i32),
            _sds(sharding, (B, MAX_LEN // BLOCK_SIZE), i32),
            _sds(sharding, (B,), jnp.bool_))


@pytest.mark.parametrize("C", [1, TOKEN_BUDGET])
@pytest.mark.parametrize("arch", ["chatglm3-6b", "starcoder2-7b"])
def test_paged_kernel_compiles_for_v5e(one_chip, arch, C):
    """The chunked-prefill kernel at published head widths (chatglm3-6b:
    hq 32, hkv 2, d 128; starcoder2-7b: hq 36, hkv 4, d 128) over a bf16
    pool lowers through Mosaic: the head-major (blk, d) K/V slab is a
    legal block for every hkv."""
    cfg = get_config(arch)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, npages, nb = MAX_BATCH, MAX_LEN // BLOCK_SIZE, NUM_BLOCKS
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = (_sds(one_chip, (b, C, hq, d), bf16),
            _sds(one_chip, (nb, hkv, BLOCK_SIZE, d), bf16),
            _sds(one_chip, (nb, hkv, BLOCK_SIZE, d), bf16),
            _sds(one_chip, (b,), i32), _sds(one_chip, (b,), i32),
            _sds(one_chip, (b, npages), i32))
    compiled = jax.jit(paged_prefill_attention_bcd).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chatglm3_bf16_init_makes_no_f32_copy(one_chip):
    """Serving weights are made in the compute dtype under ``jit``
    (``serve.init_serving_params``): the compiled initialiser writes the
    12.5 GB of bf16 weights and needs no f32 temporary of a stacked leaf
    (each of the three (28, 4096, 13696) MLP leaves is 6.3 GB in f32)."""
    cfg = get_config("chatglm3-6b").replace(remat=False,
                                            param_dtype="bfloat16")
    key = _sds(one_chip, (2,), jnp.uint32)
    mem = jax.jit(build(cfg).init_params).lower(key).compile() \
        .memory_analysis()
    assert 12e9 < mem.output_size_in_bytes < 13e9
    assert mem.temp_size_in_bytes < 0.1e9


def test_chatglm3_megastep_fits_one_v5e(one_chip):
    """The whole 28-layer chatglm3-6b megastep with bf16 weights, the
    serving pool and the kernel compiles for one chip, and its arguments
    plus temporaries fit the chip's 16 GiB."""
    cfg = get_config("chatglm3-6b").replace(remat=False,
                                            param_dtype="bfloat16")

    def place(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = place(abstract_params(cfg))
    pools = place(jax.eval_shape(
        partial(tr.init_paged_pools, cfg, NUM_BLOCKS, BLOCK_SIZE)))
    step = jax.jit(
        lambda p, pools, toks, lens, valids, tables, poison:
        tr.mixed_step_paged(p, pools, toks, lens, valids, tables, cfg,
                            poison, paged_kernel=True),
        donate_argnums=(1,))
    compiled = step.lower(params, pools, *_megastep_args(one_chip, 1)) \
        .compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 12e9          # the weights are there
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_starcoder2_tp4_megastep_fits_each_v5e_chip(topo):
    """The tensor-parallel megastep (the engine's shard_map body) for the
    whole 32-layer starcoder2-7b in bf16 over four chips: it compiles with
    the kernel and one all-reduce in the layer body, and each chip holds
    its share. MLP and embeddings replicate, so a chip holds about 12.8 GB
    of arguments, not a quarter of the 14.6 GB model."""
    tp = 4
    cfg = get_config("starcoder2-7b").replace(remat=False,
                                              param_dtype="bfloat16")
    mesh = Mesh(topo.devices[:tp], (TP,), axis_types=(AxisType.Auto,))
    params = abstract_params(cfg)
    specs = serving_param_pspecs(cfg, tp, params)
    params = jax.tree.map(
        lambda s, p: _sds(NamedSharding(mesh, p), s.shape, s.dtype),
        params, specs)
    pool_sh = NamedSharding(mesh, kv_pool_pspec())
    pools = jax.tree.map(
        lambda s: _sds(pool_sh, s.shape, s.dtype),
        jax.eval_shape(partial(tr.init_paged_pools, cfg, NUM_BLOCKS,
                               BLOCK_SIZE)))
    lcfg = cfg.replace(n_heads=cfg.n_heads // tp,
                       n_kv_heads=cfg.n_kv_heads // tp,
                       head_dim=cfg.resolved_head_dim)
    pool_specs = {"k": kv_pool_pspec(), "v": kv_pool_pspec()}
    body = jax.shard_map(
        lambda p, pools, toks, lens, valids, tables, poison:
        tr.mixed_step_paged(p, pools, toks, lens, valids, tables, lcfg,
                            poison, paged_kernel=True, axis_name=TP),
        mesh=mesh, in_specs=(specs, pool_specs, *megastep_input_pspecs()),
        out_specs=(megastep_output_pspec(), pool_specs), check_vma=False)
    step = jax.jit(body, donate_argnums=(1,))
    compiled = step.lower(
        params, pools,
        *_megastep_args(NamedSharding(mesh, P()), TOKEN_BUDGET)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    mem = compiled.memory_analysis()
    assert 12e9 < mem.argument_size_in_bytes < 13.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
