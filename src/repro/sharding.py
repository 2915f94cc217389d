"""Logical-axis sharding rules (MaxText-style), mapped onto the production
mesh axes ("pod", "data", "model").

Conventions:
  batch        -> ("pod", "data")   data parallel, pods compose with data
  vocab        -> "model"           tensor-parallel embedding / lm head
  heads        -> "model"           attention-head tensor parallelism
  kv_heads     -> "model" iff divisible, else shard head_dim ("kv_alt")
  mlp          -> "model"           FFN tensor parallelism
  experts      -> "model"           expert parallelism (all-to-all dispatch)
  embed/seq    -> None              replicated (seq-parallel is a perf knob)
  fsdp axes    -> "data"            ZeRO-style storage sharding (opt-in)
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


BATCH_AXES = ("pod", "data")


def kv_repeat(cfg, model_size: int) -> int:
    """Megatron-style KV replication factor: repeat each KV head r times so
    KV*r == TP degree, provided the GQA group splits evenly (G % r == 0).
    Cleans up attention sharding when kv_heads < model_size."""
    kv, h = cfg.n_kv_heads, cfg.n_heads
    if not kv or kv >= model_size or model_size % kv != 0:
        return 1
    r = model_size // kv
    g = h // kv
    return r if g % r == 0 else 1


@dataclass(frozen=True)
class ShardingRules:
    """logical name -> mesh axis (or tuple of axes, or None)."""
    kv_rep: int = 1
    mesh: Mesh | None = None
    rules: dict = field(default_factory=lambda: dict(
        batch=BATCH_AXES,
        seq=None,
        embed=None,
        vocab="model",
        heads="model",
        kv_heads="model",
        kv_head_dim=None,     # used when kv_heads don't divide |model|
        head_dim=None,
        mlp="model",
        heads_flat="model",   # rwkv: fused H*hd projections
        embed2=None,          # square D->D projections, output side
        experts="model",
        expert_mlp=None,
        kv_lora=None,         # MLA latent width: never split (replicated)
        ssm_inner="model",
        ssm_state=None,
        conv=None,
        fsdp=None,            # set to "data" for ZeRO storage sharding
        stack=None,           # scan-stacked layer dim
    ))

    def axis(self, name: str | None):
        if name is None:
            return None
        if name not in self.rules:
            raise KeyError(f"unknown logical axis {name!r}")
        return self.rules[name]

    def spec(self, *names: str | None) -> P:
        return P(*(self.axis(n) for n in names))

    def with_overrides(self, **kv) -> "ShardingRules":
        return ShardingRules(kv_rep=self.kv_rep, mesh=self.mesh,
                             rules={**self.rules, **kv})

    def with_kv_rep(self, r: int) -> "ShardingRules":
        return ShardingRules(kv_rep=r, mesh=self.mesh, rules=dict(self.rules))

    def with_mesh(self, mesh) -> "ShardingRules":
        return ShardingRules(kv_rep=self.kv_rep, mesh=mesh,
                             rules=dict(self.rules))


def rules_for(cfg, mesh: Mesh, *, fsdp: bool = False) -> ShardingRules:
    """Per-arch rules: resolve kv-head replication and FSDP storage.

    GQA archs whose kv_heads don't divide the TP degree either replicate KV
    heads (kv_repeat) or fall back to head_dim sharding."""
    r = ShardingRules().with_mesh(mesh)
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    r = r.with_overrides(batch=batch_axes or None)
    model_size = mesh.shape.get("model", 1)
    if cfg.n_kv_heads:
        rep = kv_repeat(cfg, model_size)
        r = r.with_kv_rep(rep)
        if (cfg.n_kv_heads * rep) % model_size != 0:
            # GQA that can't replicate to TP degree: shard head_dim instead
            r = r.with_overrides(kv_heads=None, kv_head_dim="model")
        if cfg.n_heads % model_size != 0:
            # uneven q heads (36/56 vs 16): shard head_dim for all of QKV
            r = r.with_overrides(heads=None, head_dim="model",
                                 kv_heads=None, kv_head_dim="model")
    if cfg.family.value in ("ssm", "hybrid"):
        if cfg.ssm_state and (cfg.d_inner // cfg.ssm_head_dim) % model_size:
            r = r.with_overrides(ssm_inner=None)
    if fsdp:
        r = r.with_overrides(fsdp="data")
    return r


def _axis_size(mesh_shape: dict, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh_shape.get(a, 1)
        return n
    return mesh_shape.get(axis, 1)


def fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh axis size doesn't divide (jit argument
    shardings must divide evenly; intermediates also propagate cleaner)."""
    ms = dict(mesh.shape)
    out = []
    for i, axis in enumerate(spec):
        if i >= len(shape):
            out.append(None)
            continue
        size = _axis_size(ms, axis)
        out.append(axis if size > 1 and shape[i] % size == 0
                   else (axis if size == 1 else None))
    return P(*out)


# logical dims eligible for ZeRO/FSDP storage sharding over the data axes
FSDP_CANDIDATES = ("embed", "mlp", "expert_mlp", "vocab", "heads",
                   "head_dim", "kv_heads", "kv_head_dim", "ssm_inner",
                   "heads_flat", "embed2", "experts")


def apply_fsdp(spec: P, names, shape, mesh: Mesh, fsdp_axes) -> P:
    """Shard the largest currently-unsharded eligible dim over the data
    axes (ZeRO-style parameter/optimizer storage sharding)."""
    if len(shape) < 2:
        return spec
    ms = dict(mesh.shape)
    ways = _axis_size(ms, tuple(fsdp_axes))
    best, best_size = None, 0
    for i, name in enumerate(names):
        if i >= len(shape) or spec[i] is not None:
            continue
        if name in FSDP_CANDIDATES and shape[i] % ways == 0 \
                and shape[i] > best_size:
            best, best_size = i, shape[i]
    if best is None:
        return spec
    out = list(spec)
    out[best] = tuple(fsdp_axes)
    return P(*out)


def named_sharding(mesh: Mesh, rules: ShardingRules, *names,
                   shape=None, fsdp_axes=None) -> NamedSharding:
    spec = rules.spec(*names)
    if shape is not None:
        spec = fit_spec(spec, shape, mesh)
        if fsdp_axes:
            spec = apply_fsdp(spec, names, shape, mesh, fsdp_axes)
    return NamedSharding(mesh, spec)


def constrain(x, rules: ShardingRules, *names):
    """with_sharding_constraint by logical names. When the rules carry a
    mesh, the constraint is a full NamedSharding (no thread-local mesh
    context needed) fitted to the value's shape."""
    spec = rules.spec(*names)
    if rules.mesh is not None:
        spec = fit_spec(spec, x.shape, rules.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(rules.mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def tree_shardings(mesh: Mesh, rules: ShardingRules, logical_tree,
                   shapes_tree=None, fsdp_axes=None):
    """Map a pytree of logical-name tuples to NamedShardings; if a parallel
    shapes tree is given, fit each spec to the leaf shape (and optionally
    apply FSDP storage sharding over `fsdp_axes`)."""
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(n, (str, type(None))) for n in x)
    if shapes_tree is None:
        return jax.tree_util.tree_map(
            lambda names: named_sharding(mesh, rules, *names),
            logical_tree, is_leaf=is_leaf)
    flat_axes, tdef = jax.tree_util.tree_flatten(logical_tree,
                                                 is_leaf=is_leaf)
    flat_shapes = tdef.flatten_up_to(shapes_tree)
    out = [named_sharding(mesh, rules, *a, shape=s.shape,
                          fsdp_axes=fsdp_axes)
           for a, s in zip(flat_axes, flat_shapes)]
    return tdef.unflatten(out)
