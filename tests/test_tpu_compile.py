"""Compile the main serving path for a TPU v5e chip that is described, not
attached: the TPU compiler refuses what the chip would refuse (a program
that does not fit its HBM, an unaligned kernel tile) without a chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. Nothing here runs on a device; shapes stand in for arrays.
"""
import os
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
from repro.train.steps import make_paged_serve_step, make_serve_step

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
