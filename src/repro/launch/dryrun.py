import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes, record memory/cost/collective analysis for §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all            # everything

Results cached incrementally in experiments/dryrun.json; existing cells are
skipped unless --force.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

import jax.numpy as jnp

from repro.config import SHAPES, ShapeKind, TrainConfig, shapes_for
from repro.configs import get_config, all_arch_ids
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models.registry import get_api
from repro.perf.hlo_cost import analyze as hlo_analyze
from repro.perf.roofline import roofline_terms, model_flops
from repro.sharding import rules_for, tree_shardings, named_sharding
from repro.train.steps import make_train_step, make_prefill_step, \
    make_serve_step

RESULTS = Path(__file__).resolve().parents[3] / "experiments" / "dryrun.json"


def shapes_and_axes(init_fn, rng, cfg):
    """eval_shape the param init; capture the logical-axes tree (python side
    effect during trace) without allocating anything."""
    box = {}
    def wrapper(r):
        params, axes = init_fn(r, cfg)
        box["axes"] = axes
        return params
    shapes = jax.eval_shape(wrapper, rng)
    return shapes, box["axes"]


def batch_sharding_tree(cfg, mesh, rules, specs):
    """NamedShardings for a batch/decode spec dict."""
    def spec_for(path, leaf):
        name = path[0]
        if name in ("tokens", "labels"):
            return ("batch", "seq")
        if name in ("embeds", "enc_out"):
            return ("batch", "seq", "embed")
        if name == "token":
            return ("batch", None)
        if name == "cache_len":
            return ("batch",)
        raise KeyError(name)

    out = {}
    for k, v in specs.items():
        if k == "cache":
            ax = get_api(cfg).cache_axes(cfg)
            out[k] = tree_shardings(mesh, rules, ax, v)
        else:
            out[k] = named_sharding(mesh, rules, *spec_for((k,), v),
                                    shape=v.shape)
    return out


def _cast_tree_shapes(shapes, dtype):
    """ShapeDtypeStruct tree with floating leaves cast (bf16 serving)."""
    def one(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(s.shape, dtype)
        return s
    return jax.tree_util.tree_map(one, shapes)


def pick_microbatches(cfg, shape, batch_ways: int) -> int:
    """Grad-accumulation depth so saved activations fit HBM: target <=2
    sequences per device per microbatch for the big archs."""
    per_dev = max(1, shape.global_batch // batch_ways)
    target = 1 if cfg.d_model * cfg.n_layers >= 48 * 4096 else 2
    mb = max(1, per_dev // target)
    while shape.global_batch % (mb * batch_ways) and mb > 1:
        mb -= 1
    return mb


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fsdp: bool = True, donate: bool = True,
             microbatches: int | None = None,
             serve_dtype: str = "bfloat16",
             rules_overrides: dict | None = None,
             cfg_overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rules = rules_for(cfg, mesh, fsdp=fsdp)
    # small batches (long_500k B=1) cannot shard the batch axis -> replicate
    batch_ways = 1
    for a in ("pod", "data"):
        batch_ways *= mesh.shape.get(a, 1)
    if shape.global_batch % batch_ways != 0:
        rules = rules.with_overrides(batch=None)
        batch_ways = 1
    if rules_overrides:
        rules = rules.with_overrides(**rules_overrides)
    fsdp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape) \
        if fsdp else None

    api = get_api(cfg)
    rng = jax.random.PRNGKey(0)
    p_shapes, p_axes = shapes_and_axes(api.init, rng, cfg)
    if shape.kind != ShapeKind.TRAIN:
        p_shapes = _cast_tree_shapes(p_shapes, jnp.dtype(serve_dtype))
    p_shard = tree_shardings(mesh, rules, p_axes, p_shapes,
                             fsdp_axes=fsdp_axes)
    specs = input_specs(cfg, shape, kv_rep=rules.kv_rep)
    b_shard = batch_sharding_tree(cfg, mesh, rules, specs)

    mb = microbatches if microbatches is not None else (
        pick_microbatches(cfg, shape, batch_ways)
        if shape.kind == ShapeKind.TRAIN else 1)

    t0 = time.time()
    with mesh:
        if shape.kind == ShapeKind.TRAIN:
            ts, opt = make_train_step(cfg, rules,
                                      TrainConfig(microbatches=mb))
            o_shapes = jax.eval_shape(opt.init, p_shapes)
            o_shard = tree_shardings(mesh, rules, opt.state_axes(p_axes),
                                     o_shapes, fsdp_axes=fsdp_axes)
            jitted = jax.jit(
                ts,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(p_shapes, o_shapes, specs)
        elif shape.kind == ShapeKind.PREFILL:
            pf = make_prefill_step(cfg, rules)
            jitted = jax.jit(pf, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(p_shapes, specs)
        else:  # decode
            sv = make_serve_step(cfg, rules)
            cache_shard = b_shard["cache"]
            in_sh = [p_shard, cache_shard, b_shard["token"],
                     b_shard["cache_len"]]
            args = [p_shapes, specs["cache"], specs["token"],
                    specs["cache_len"]]
            if "enc_out" in specs:
                in_sh.append(b_shard["enc_out"])
                args.append(specs["enc_out"])
            jitted = jax.jit(
                sv, in_shardings=tuple(in_sh),
                out_shardings=(None, cache_shard),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    hc = hlo_analyze(hlo)          # trip-count-aware flops/bytes/collectives
    mf = model_flops(cfg, shape)
    rl = roofline_terms(hc.flops, hc.hbm_bytes, hc.coll_wire_bytes, mf, chips)

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "fsdp": fsdp,
        "microbatches": mb,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_dev": mem.argument_size_in_bytes,
            "output_bytes_dev": mem.output_size_in_bytes,
            "temp_bytes_dev": mem.temp_size_in_bytes,
            "alias_bytes_dev": mem.alias_size_in_bytes,
            "peak_bytes_dev": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
        },
        "cost": {
            "flops_dev": hc.flops,
            "hbm_bytes_dev": hc.hbm_bytes,
            # lower bound: every live buffer touched exactly once
            "hbm_bytes_dev_lower": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes),
            "xla_flops_dev_nolooptrip": float(cost.get("flops", 0.0)),
            "unknown_trip_loops": hc.unknown_trip_loops,
        },
        "collectives": {
            "wire_bytes_dev": hc.coll_wire_bytes,
            "simple_bytes_dev": hc.coll_simple_bytes,
            "by_op": hc.coll_by_op,
        },
        "roofline": rl.to_dict(),
    }


def cell_key(arch, shape_name, multi_pod, tag=""):
    return f"{arch}|{shape_name}|{'multi' if multi_pod else 'single'}{tag}"


def load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_results(res: dict) -> None:
    RESULTS.parent.mkdir(parents=True, exist_ok=True)
    RESULTS.write_text(json.dumps(res, indent=1, sort_keys=True))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="disable ZeRO/FSDP storage sharding (default on)")
    args = ap.parse_args()
    args.fsdp = not args.no_fsdp

    archs = all_arch_ids() if (args.all or not args.arch) \
        else [args.arch]
    res = load_results()
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        cfg = get_config(arch)
        shape_names = ([args.shape] if args.shape and not args.all
                       else [s.name for s in shapes_for(cfg)])
        for sn in shape_names:
            if SHAPES[sn] not in shapes_for(cfg):
                print(f"SKIP {arch} {sn}: long-context needs sub-quadratic "
                      f"attention (family={cfg.family.value})", flush=True)
                continue
            for mp in meshes:
                key = cell_key(arch, sn, mp, "" if args.fsdp else "|nofsdp")
                if key in res and res[key].get("status") == "ok" \
                        and not args.force:
                    print(f"CACHED {key}", flush=True)
                    continue
                print(f"RUN {key} ...", flush=True)
                try:
                    out = run_cell(arch, sn, mp, fsdp=args.fsdp)
                except Exception as e:  # noqa: BLE001 — record failures
                    out = {"arch": arch, "shape": sn,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                res[key] = out
                save_results(res)
                if out["status"] == "ok":
                    r = out["roofline"]
                    print(f"  ok: compute={r['compute_s']*1e3:.1f}ms "
                          f"memory={r['memory_s']*1e3:.1f}ms "
                          f"collective={r['collective_s']*1e3:.1f}ms "
                          f"bottleneck={r['bottleneck']} "
                          f"peak={out['memory']['peak_bytes_dev']/2**30:.2f}GiB "
                          f"(compile {out['compile_s']}s)", flush=True)
                else:
                    print(f"  ERROR: {out['error']}", flush=True)


if __name__ == "__main__":
    main()
