"""Compile a configuration's paged serve step and weight maker (both
through the architecture module its file names) for one TPU v5e chip
that is described, not attached, and print what the compiler says they
need (``memory_analysis``).

  JAX_PLATFORMS=cpu python bench/rehearse.py internlm2-20b [--blocks N ...]
  JAX_PLATFORMS=cpu python bench/rehearse.py starcoder2-7b --search

With ``--search`` it finds the largest KV arena (in blocks) whose step
fits the chip's HBM with ``--margin-gib`` to spare: two compiles fix the
step's bytes per block, a third checks the size they predict. Nothing
runs; the configuration file is not changed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402

# HBM of one v5e chip that XLA may allocate (the compiler's own limit)
V5E_HBM_BYTES = 15.75 * 2**30
GIB = 2**30


def _chip():
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh, SingleDeviceSharding
    import numpy as np

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return SingleDeviceSharding(topo.devices[0]), mesh


def _peak(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _report(what: str, ma) -> dict:
    row = {"what": what,
           "argument_gib": ma.argument_size_in_bytes / GIB,
           "output_gib": ma.output_size_in_bytes / GIB,
           "temp_gib": ma.temp_size_in_bytes / GIB,
           "alias_gib": ma.alias_size_in_bytes / GIB,
           "peak_gib": _peak(ma) / GIB}
    print(json.dumps(row), flush=True)
    return row


def compile_weights(arch, m, one_chip):
    fn = jax.jit(lambda k: arch.program_params(m, k), out_shardings=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    return fn.lower(key).compile().memory_analysis()


def compile_step(arch, cfg: dict, name: str, n_blocks: int, one_chip,
                 mesh):
    from repro.models import transformer
    from repro.sharding import rules_for
    from repro.train.steps import make_paged_serve_step

    m = arch.dims(cfg)
    sv = cfg["serving"]
    mc = arch.program_config(name, m)
    on = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(lambda k: arch.program_params(m, k),
                               jax.random.PRNGKey(0)))
    arenas = on(jax.eval_shape(lambda: transformer.init_paged_arena(
        mc, n_blocks, sv["block_size"])))
    b, mb = sv["n_slots"], sv["max_blocks_per_seq"]
    bt = jax.ShapeDtypeStruct((b, mb), jnp.int32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    cl = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    step = jax.jit(make_paged_serve_step(mc, rules_for(mc, mesh)))
    return step.lower(params, arenas, bt, tok, cl).compile().memory_analysis()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--blocks", type=int, nargs="*", default=[])
    ap.add_argument("--search", action="store_true")
    ap.add_argument("--margin-gib", type=float, default=0.25)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    file = f"bench/configs/{args.config}.json"
    cfg = json.loads((BENCH.parent / file).read_text())
    arch = harness.load_arch(cfg, file)
    one_chip, mesh = _chip()
    _report("weights", compile_weights(arch, arch.dims(cfg), one_chip))
    blocks = args.blocks or [cfg["serving"]["n_blocks"]]

    def step(n):
        return compile_step(arch, cfg, args.config, n, one_chip, mesh)
    rows = {n: _report(f"step n_blocks={n}", step(n)) for n in blocks}
    if args.search:
        lo, hi = 256, 1024
        for n in (lo, hi):
            if n not in rows:
                rows[n] = _report(f"step n_blocks={n}", step(n))
        per_block = (rows[hi]["peak_gib"] - rows[lo]["peak_gib"]) / (hi - lo)
        room = V5E_HBM_BYTES / GIB - args.margin_gib - rows[lo]["peak_gib"]
        best = lo + int(room / per_block)
        row = _report(f"step n_blocks={best}", step(best))
        print(json.dumps({"per_block_gib": per_block, "n_blocks": best,
                          "headroom_gib": V5E_HBM_BYTES / GIB
                          - row["peak_gib"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
