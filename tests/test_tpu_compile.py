"""Compile the main serving path for a TPU v5e chip that is described, not
attached: the TPU compiler refuses what the chip would refuse (a program
that does not fit its HBM, an unaligned kernel tile) without a chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. Nothing here runs on a device; shapes stand in for arrays.
"""
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.launch.serve import serving_config
from repro.models.registry import get_api
from repro.sharding import rules_for
from repro.serving.engine import CHUNK, alias_bytes, compile_programs
from repro.train.steps import (make_paged_prefill_chunk, make_paged_serve_step,
                               make_serve_step)

# HBM of one v5e chip that XLA may allocate (the compiler's own limit:
# "... of 15.75G hbm")
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _param_shapes(cfg):
    api = get_api(cfg)
    return jax.eval_shape(lambda r: api.init(r, cfg)[0],
                          jax.random.PRNGKey(0))


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_rwkv6_3b_serve_step_fits_one_chip(one_chip, chip_mesh):
    """The whole published rwkv6-3b decode step with bf16 serving weights:
    6.2 GB of arguments, where f32 weights needed 16.67G of 15.75G."""
    cfg = serving_config(get_config("rwkv6-3b"))
    assert cfg.n_layers == 32 and cfg.d_model == 2560
    params = _on(_param_shapes(cfg), one_chip)
    cache = _on(jax.eval_shape(lambda: get_api(cfg).init_cache(cfg, 1, 256)),
                one_chip)
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    cl = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    step = jax.jit(make_serve_step(cfg, rules_for(cfg, chip_mesh)))
    compiled = step.lower(params, cache, tok, cl).compile()
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert weights < 2.1 * cfg.param_count()          # 2-byte weights
    assert _peak_bytes(compiled) < V5E_HBM_BYTES


def test_jitted_init_holds_stacked_weights_once(one_chip):
    """The serving init is one jitted program: the stacked layer weights
    are written in place, so its scratch stays far below its output
    (eager ``stack_layers`` holds every per-layer array beside the stack).
    rwkv6-3b widths, depth cut to 2 layers to keep the compile short."""
    cfg = serving_config(replace(get_config("rwkv6-3b"), n_layers=2))
    api = get_api(cfg)
    init = jax.jit(lambda r: api.init(r, cfg)[0], out_shardings=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    m = init.lower(rng).compile().memory_analysis()
    assert m.output_size_in_bytes > 0.9e9
    assert m.temp_size_in_bytes < 0.05 * m.output_size_in_bytes


def test_internlm2_20b_paged_step_compiles(one_chip, chip_mesh):
    """The continuous engine's paged decode step at internlm2-20b's
    published widths, depth cut to 4 layers: 8 slots over 256 KV blocks
    of 16 positions, bf16."""
    cfg = serving_config(replace(get_config("internlm2-20b"), n_layers=4))
    from repro.models import transformer
    params = _on(_param_shapes(cfg), one_chip)
    arenas = _on(jax.eval_shape(
        lambda: transformer.init_paged_arena(cfg, 256, 16)), one_chip)
    bt = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    cl = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    step = jax.jit(make_paged_serve_step(cfg, rules_for(cfg, chip_mesh)))
    compiled = step.lower(params, arenas, bt, tok, cl).compile()
    assert _peak_bytes(compiled) < V5E_HBM_BYTES


def _compile_prefill_chunk(cfg, chip_mesh, one_chip, n_blocks, max_blocks):
    """The engine's prefill chunk program, jitted as ``make_engine`` jits
    it (arenas donated), compiled for the chip. Returns the compiled
    program and the arenas' bytes."""
    from repro.models import transformer
    params = _on(_param_shapes(cfg), one_chip)
    arenas = _on(jax.eval_shape(
        lambda: transformer.init_paged_arena(cfg, n_blocks, 16)), one_chip)
    row = jax.ShapeDtypeStruct((1, max_blocks), jnp.int32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32, sharding=one_chip)
    one = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    chunk = jax.jit(make_paged_prefill_chunk(cfg, rules_for(cfg, chip_mesh)),
                    donate_argnums=1)
    compiled = chunk.lower(params, arenas, row, tok, one, one).compile()
    arena_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(arenas))
    return compiled, arena_bytes


def _assert_chunk_program(compiled, arena_bytes):
    # the arenas are updated in place (donation took), the program fits,
    # and its name keeps it apart from the decode step in a device trace
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= arena_bytes
    assert _peak_bytes(compiled) < V5E_HBM_BYTES
    name = compiled.as_text().split(None, 2)[1]
    assert "prefill_chunk" in name and "paged_serve_step" not in name


def test_internlm2_20b_prefill_chunk_compiles(one_chip, chip_mesh):
    """The engine's CHUNK-token prefill program at internlm2-20b's
    published widths, depth cut to 4 layers, over the paged step's 256
    KV blocks of 16 positions, bf16."""
    cfg = serving_config(replace(get_config("internlm2-20b"), n_layers=4))
    _assert_chunk_program(*_compile_prefill_chunk(cfg, chip_mesh, one_chip,
                                                  256, 128))


def test_starcoder2_7b_prefill_chunk_fits_one_chip(one_chip, chip_mesh):
    """The whole published starcoder2-7b (bf16, 13.8 GiB of weights) with
    its 769-block arena of 48 blocks a sequence: the chunk program fits
    beside the weights only because the arenas are donated."""
    cfg = serving_config(get_config("starcoder2-7b"))
    assert cfg.n_layers == 32 and cfg.d_model == 4608
    _assert_chunk_program(*_compile_prefill_chunk(cfg, chip_mesh, one_chip,
                                                  769, 48))


def test_moonlight_paged_step_and_chunk_compile(one_chip, chip_mesh):
    """Latent attention and dropless routing: the paged decode step and
    the prefill chunk at moonlight-16b-a3b's published widths, one chip's
    16 of 64 experts, depth cut to 3 (the dense layer and two expert
    layers): 8 slots over 256 latent blocks of 16 positions, bf16."""
    from repro.models import transformer
    cfg = serving_config(replace(get_config("moonlight-16b-a3b"), n_layers=3,
                                 experts_held=(0, 16)))
    params = _on(_param_shapes(cfg), one_chip)
    arenas = _on(jax.eval_shape(
        lambda: transformer.init_paged_arena(cfg, 256, 16)), one_chip)
    assert arenas["latent"].shape == (3, 256, 16, 512)
    assert arenas["rope"].shape == (3, 256, 16, 64)
    bt = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    cl = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    step = jax.jit(make_paged_serve_step(cfg, rules_for(cfg, chip_mesh)))
    lowered = step.lower(params, arenas, bt, tok, cl)
    hits = lowered.out_info[2]
    assert hits.shape == (2, 16) and hits.dtype == jnp.int32
    compiled = lowered.compile()
    assert _peak_bytes(compiled) < V5E_HBM_BYTES
    # the latent arena enters and leaves in the row-major layout the step
    # computes in, so no step converts the whole arena (a 576-wide entry
    # did, twice a step)
    entry_layout = compiled.as_text().split("\n", 1)[0]
    assert "bf16[3,256,16,512]{3,2,1,0:" in entry_layout
    _assert_chunk_program(*_compile_prefill_chunk(cfg, chip_mesh, one_chip,
                                                  256, 128))


# (configuration, depth, the cell's arena blocks, slots, blocks a row):
# arenas larger than the chip's on-chip memory, which a small arena is
# staged through (its copies would blur what this checks)
IN_PLACE_CASES = [("internlm2-20b", 4, 2817, 32, 88),
                  ("moonlight-16b-a3b", 27, 4225, 48, 88)]


@pytest.mark.parametrize("name,layers,n_blocks,slots,row", IN_PLACE_CASES,
                         ids=[c[0] for c in IN_PLACE_CASES])
def test_engine_programs_update_the_arenas_in_place(one_chip, chip_mesh,
                                                    name, layers, n_blocks,
                                                    slots, row):
    """The decode step and the prefill chunk, jitted and compiled as
    ``make_engine`` does (arenas donated), at a cell's arena size: both
    alias the arenas to their outputs, so no step copies them in, and no
    instruction has a whole layer's arena shape ([NB,BS,...],
    [1,NB,BS,...] or [NB*BS,...]), so no layer is sliced out, scattered
    into or written back: each program writes only its new entries and
    gathers its rows' pages at (layer, block)."""
    from repro.models import transformer
    held = {"experts_held": (0, 16)} if name.startswith("moonlight") else {}
    cfg = serving_config(replace(get_config(name), n_layers=layers, **held))
    rules = rules_for(cfg, chip_mesh)
    arenas = _on(jax.eval_shape(
        lambda: transformer.init_paged_arena(cfg, n_blocks, 16)), one_chip)
    arena_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(arenas))
    whole = re.compile("|".join(
        rf"\[(1,)?{n_blocks},16{w}\]|\[{n_blocks * 16}{w}\]"
        for w in {"".join(f",{d}" for d in a.shape[3:])
                  for a in jax.tree_util.tree_leaves(arenas)}))
    for compiled in compile_programs(
            make_paged_serve_step(cfg, rules),
            make_paged_prefill_chunk(cfg, rules), _on(_param_shapes(cfg),
                                                      one_chip),
            arenas, n_slots=slots, max_blocks=row, sharding=one_chip):
        assert alias_bytes(compiled) >= arena_bytes
        assert _peak_bytes(compiled) < V5E_HBM_BYTES
        found = [ln.strip()[:120] for ln in compiled.as_text().splitlines()
                 if whole.search(ln)]
        assert not found, found[:4]


def test_invoke_step_compiles(one_chip, gsys):
    """A device-initiated syscall: the jitted step's io_callback (ordered,
    as WORK_ITEM batches are) lowers and compiles for the chip."""
    from repro.core.genesys import Granularity, Ordering, Sys
    from repro.core.genesys.invoke import pack_args

    args = jnp.stack([pack_args(3, 1, 4096, 4096 * i, 4096 * i)
                      for i in range(8)])

    def step(x, args):
        res = gsys.invoke(Sys.PREAD64, args,
                          granularity=Granularity.WORK_ITEM,
                          ordering=Ordering.STRONG, blocking=True, deps=x)
        return res.tie(x * 2.0), res.ret64()

    x = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip)
    a = jax.ShapeDtypeStruct(args.shape, args.dtype, sharding=one_chip)
    hlo = jax.jit(step).lower(x, a).compile().as_text()
    # the call leaves the chip as host transfers: the args go out, the
    # [8, 2] return words come back
    assert "is_host_transfer=true" in hlo
    assert "s32[8,6,2]" in hlo and "s32[8,2]" in hlo
