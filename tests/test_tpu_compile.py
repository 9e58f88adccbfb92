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
import re
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
from repro.kernels.paged_attention.kernel import (SCOPED_VMEM_BYTES,
                                                  kernel_plan,
                                                  paged_prefill_attention_bcd)
from repro.models import abstract_params, build
from repro.models import transformer as tr
from repro.serving import budget_buckets

# the serving shapes chip_smoke.py runs: --block-size 16 (one bf16 sublane
# tile per (blk, head_dim) slab) and --token-budget 256, whose widest
# bucket is C == 256; decode rows are C == 1
BLOCK_SIZE = 16
TOKEN_BUDGET = 256
MAX_BATCH, NUM_BLOCKS, MAX_LEN = 4, 1024, 3072
V5E_HBM_BYTES = 16 * 2**30
# the benchmark's serving shapes (bench/configs/chatglm3-6b.json): 8 rows,
# max_len 8192 in pages of 16, 3456 blocks, budget 1024
SERVE_BATCH, SERVE_PAGES, SERVE_BLOCKS = 8, 8192 // BLOCK_SIZE, 3456
SERVE_BUCKETS = budget_buckets(1024)


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


@pytest.mark.parametrize("C", SERVE_BUCKETS)
@pytest.mark.parametrize("arch", ["chatglm3-6b", "starcoder2-7b"])
def test_paged_kernel_compiles_for_v5e(one_chip, arch, C):
    """The chunked-prefill kernel at published head widths (chatglm3-6b:
    hq 32, hkv 2, d 128; starcoder2-7b: hq 36, hkv 4, d 128) over a bf16
    pool lowers through Mosaic at every bucket of a 1024-token budget,
    with the benchmark's table of 512 pages, inside Mosaic's default
    scoped VMEM: the per-page copies of (blk, d) slabs, the (2, P, blk, d)
    block buffers and the query tiles the plan picks are all legal."""
    cfg = get_config(arch)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, npages, nb = SERVE_BATCH, SERVE_PAGES, SERVE_BLOCKS
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = (_sds(one_chip, (b, C, hq, d), bf16),
            _sds(one_chip, (nb, hkv, BLOCK_SIZE, d), bf16),
            _sds(one_chip, (nb, hkv, BLOCK_SIZE, d), bf16),
            _sds(one_chip, (b,), i32), _sds(one_chip, (b,), i32),
            _sds(one_chip, (b, npages), i32))
    compiled = jax.jit(paged_prefill_attention_bcd).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["chatglm3-6b", "starcoder2-7b"])
def test_kernel_plan_fits_scoped_vmem(arch):
    """The kernel's plan at every bucket of a 1024-token budget, for the
    whole model and for a tp=2 shard (half the heads of each kind): its
    buffers fit the scoped VMEM it assumes, a tile holds the whole GQA
    group for its positions in sublane-aligned rows, the tiles cover the
    chunk, and a decode tile is the group alone."""
    cfg = get_config(arch)
    d = cfg.resolved_head_dim
    for tp in (1, 2):
        hq, hkv = cfg.n_heads // tp, cfg.n_kv_heads // tp
        g = hq // hkv
        for C in SERVE_BUCKETS:
            plan = kernel_plan(C=C, hq=hq, hkv=hkv, blk=BLOCK_SIZE, d=d,
                               dv=d, npages=SERVE_PAGES,
                               q_dtype=jnp.bfloat16, kv_dtype=jnp.bfloat16)
            assert plan.vmem_bytes <= SCOPED_VMEM_BYTES
            assert plan.rows % 16 == 0
            assert g * plan.tile_positions <= plan.rows \
                < g * plan.tile_positions + 16
            assert (plan.query_tiles - 1) * plan.tile_positions < C \
                <= plan.query_tiles * plan.tile_positions
            assert 1 <= plan.pages_per_block <= SERVE_PAGES
        decode = kernel_plan(C=1, hq=hq, hkv=hkv, blk=BLOCK_SIZE, d=d,
                             dv=d, npages=SERVE_PAGES, q_dtype=jnp.bfloat16,
                             kv_dtype=jnp.bfloat16)
        assert (decode.tile_positions, decode.query_tiles) == (1, 1)
        assert decode.rows == -(-g // 16) * 16


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


# one HLO instruction: name, output array shape (not a tuple), opcode
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*\w+\[([\d,]*)\]\S*\s+([\w-]+)\(")
# ops that name or reuse a buffer without writing a new one; the scatter
# (alone or as a fusion's root) writes the carried pool in place
_NO_NEW_BUFFER = {"parameter", "get-tuple-element", "bitcast", "scatter"}


def _pool_sized_ops(hlo: str, sizes) -> list:
    """(name, shape, opcode) of every instruction whose output holds as
    many elements as one of ``sizes`` and that materialises it: anything
    but a parameter, a tuple element, a bitcast, a scatter, or a fusion
    whose root is a scatter."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%(\S+)\s+\(.*\{\s*$", line)
        if head:
            comp = head.group(1)
        m = _HLO_INSTR.match(line)
        if m and line.lstrip().startswith("ROOT") and comp:
            roots[comp] = m.group(3)
    found = []
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        if n not in sizes or op in _NO_NEW_BUFFER:
            continue
        calls = re.search(r"calls=%([^,\s]+)", line)
        if op == "fusion" and calls and roots.get(calls.group(1)) \
                == "scatter":
            continue
        found.append((name, dims, op))
    return found


@pytest.mark.parametrize("C", [1, 1024])
def test_chatglm3_megastep_keeps_the_pool_in_place(one_chip, C):
    """The chatglm3-6b megastep at the benchmark's serving shapes (8 rows,
    512 pages, 3456 blocks of 16) at decode width and at the widest
    bucket carries each (28, 3456, 2, 16, 128) pool through its layer
    scan as one buffer: no compiled op copies, slices, relays or
    restacks the stack or one layer's slice of it (28.3 MB, in either
    layout), and the temporaries hold no copy of a pool. A step that
    scans the pools as xs/ys fails both checks: it relays and restacks
    every layer's slice and copies both 0.79 GB stacks after the loop
    (temporaries 1.59e9 B at C = 1, 2.22e9 B at C = 1024)."""
    cfg = get_config("chatglm3-6b").replace(remat=False,
                                            param_dtype="bfloat16")

    def place(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = place(abstract_params(cfg))
    pools = place(jax.eval_shape(
        partial(tr.init_paged_pools, cfg, SERVE_BLOCKS, BLOCK_SIZE)))
    L, nb, hkv, blk, hd = pools["k"].shape
    i32, B = jnp.int32, SERVE_BATCH
    rows = (_sds(one_chip, (B, C), i32), _sds(one_chip, (B,), i32),
            _sds(one_chip, (B,), i32), _sds(one_chip, (B, SERVE_PAGES), i32),
            _sds(one_chip, (B,), jnp.bool_))
    step = jax.jit(
        lambda p, pools, toks, lens, valids, tables, poison:
        tr.mixed_step_paged(p, pools, toks, lens, valids, tables, cfg,
                            poison, paged_kernel=True),
        donate_argnums=(1,))
    compiled = step.lower(params, pools, *rows).compile()
    layer = nb * hkv * blk * hd
    assert _pool_sized_ops(compiled.as_text(), {L * layer, layer}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


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
