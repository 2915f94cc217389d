"""Smoke test of the GENESYS serving path on one TPU chip.

  python3 chip_smoke.py                # phases (a)-(d) on one chip
  python3 chip_smoke.py --four-chips   # data-parallel training only

Phases, each printing its own ``[phase]`` lines:

  (a) device    the first JAX device must be a TPU; there is no fallback.
  (b) syscalls  a jitted step preads a file written from ``--seed``
                through ``Genesys.invoke``: on the doorbell path, through
                the uring ring, and as an ordered WORK_ITEM batch. The
                bytes read must equal the file.
  (c) rwkv6-3b  the whole published model behind the GENESYS UDP server
                (``launch/serve.py``), eager and ``--batch-decode``. Every
                request must be answered with the tokens that calling the
                same jitted ``serve_step`` directly gives.
  (d) engine    the ``--continuous`` engine on internlm2-20b at its
                published widths, depth cut to 4 layers; every request
                must be answered.

``--four-chips`` runs only the data-parallel ``Trainer`` (GENESYS loader)
at rwkv6-3b widths, depth cut to 4 layers, on four chips and on one, and
compares the losses step by step.

Any failure raises and exits non-zero. The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import socket
import sys
import tempfile
import threading
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PROMPT_LEN = 8
BUDGET = 16
EAGER_REQUESTS = 4
BATCH_REQUESTS = 8
ENGINE_REQUESTS = 8
ENGINE_LAYERS = 4
TRAIN_LAYERS = 4
TRAIN_STEPS = 4
# 1-chip vs 4-chip losses differ only by reduction order and the AdamW
# steps it perturbs: 1% of a loss near ln(vocab) is far above that and
# far below what a wrong sharding (a batch counted twice or not at all)
# moves it by
TRAIN_LOSS_RTOL = 1e-2
REPLY_TIMEOUT_S = 300.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ------------------------------------------------------------ (a) device --

def phase_device(min_count: int = 1) -> dict:
    """The device triple of the contract line; exits 1 without a TPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < min_count:
        print(f"[device] FAIL: need {min_count} TPU device(s), JAX found "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr, flush=True)
        sys.exit(1)
    log("device", f"ok {info}")
    return info


# ---------------------------------------------------------- (b) syscalls --

def phase_syscalls(seed: int, workdir: Path, *, rows: int = 8,
                   row_bytes: int = 4096) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.genesys import (Genesys, GenesysConfig, Granularity,
                                    Ordering, Sys)
    from repro.core.genesys.invoke import pack_args

    size = rows * row_bytes
    data = np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    path = workdir / "pread.bin"
    path.write_bytes(data)
    gsys = Genesys(GenesysConfig(n_workers=2))
    try:
        ph = gsys.heap.register_bytes(str(path).encode())
        fd = gsys.call(Sys.OPEN, ph, os.O_RDONLY, 0)
        gsys.heap.release(ph)
        check(fd >= 0, f"open({path}) returned {fd}")
        whole = dict(granularity=Granularity.WORK_GROUP,
                     ordering=Ordering.STRONG)
        paths = {
            "doorbell": (lambda bh: pack_args(fd, bh, size, 0),
                         dict(whole, via_ring=False), size),
            "ring": (lambda bh: pack_args(fd, bh, size, 0),
                     dict(whole, via_ring=True), size),
            "work_item": (lambda bh: jnp.stack(
                [pack_args(fd, bh, row_bytes, i * row_bytes, i * row_bytes)
                 for i in range(rows)]),
                dict(granularity=Granularity.WORK_ITEM,
                     ordering=Ordering.STRONG), row_bytes),
        }
        x = jnp.arange(4, dtype=jnp.float32)
        for name, (make_args, kw, per_call) in paths.items():
            bh = gsys.heap.new_buffer(size)
            args = make_args(bh)

            def step(x, args=args, kw=kw):
                res = gsys.invoke(Sys.PREAD64, args, blocking=True,
                                  deps=x, **kw)
                return res.tie(x * 2.0), res.ret64()

            fn = jax.jit(step)
            t0 = time.perf_counter()
            y, n = jax.block_until_ready(fn(x))
            first = time.perf_counter() - t0
            got = np.asarray(gsys.heap.resolve(bh)).tobytes()[:size]
            check(got == data, f"{name}: pread bytes differ from the file")
            check(np.all(np.asarray(n) == per_call),
                  f"{name}: pread returned {np.asarray(n).tolist()}, "
                  f"expected {per_call} per call")
            check(np.array_equal(np.asarray(y), np.asarray(x) * 2.0),
                  f"{name}: step result after the call is wrong")
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            again = time.perf_counter() - t0
            gsys.heap.release(bh)
            log("syscalls", f"{name}: ok, {size} bytes match the file; "
                            f"first call {first:.3f}s (compile + call), "
                            f"second call {again * 1e3:.2f}ms")
        gsys.call(Sys.CLOSE, fd)
    finally:
        gsys.shutdown()


# ------------------------------------------------- UDP clients + server --

def _drive_server(gsys, srv, run_serve, requests: list[np.ndarray]
                  ) -> dict[int, list[int]]:
    """Client threads send ``requests`` ([budget, tag, prompt...] int32)
    to ``srv``; then ``run_serve(reply_port)`` serves them on its own
    thread. Returns ``{tag: tokens}`` of every reply. The requests wait in
    the socket before the loop starts, so the first poll takes them all
    (one bucket on the batch-decode path)."""
    port = gsys.table._sockets[srv.fd].getsockname()[1]
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(0.5)
    errors: list[BaseException] = []

    def client(req: np.ndarray) -> None:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.sendto(req.astype(np.int32).tobytes(), ("127.0.0.1", port))

    def server() -> None:
        try:
            run_serve(rx.getsockname()[1])
        except BaseException as e:   # re-raised on the main thread
            errors.append(e)

    try:
        clients = [threading.Thread(target=client, args=(r,))
                   for r in requests]
        for t in clients:
            t.start()
        for t in clients:
            t.join(30)
            check(not t.is_alive(), "a client thread did not finish")
        th = threading.Thread(target=server, daemon=True)
        th.start()
        replies: dict[int, list[int]] = {}
        deadline = time.monotonic() + REPLY_TIMEOUT_S
        while len(replies) < len(requests) and time.monotonic() < deadline:
            try:
                data, _ = rx.recvfrom(65536)
            except socket.timeout:
                if not th.is_alive():
                    break        # the loop ended: no reply is coming
                continue
            r = np.frombuffer(data, np.int32).tolist()
            replies[r[0]] = r[1:]
        th.join(REPLY_TIMEOUT_S)
    finally:
        rx.close()
    if errors:
        raise errors[0]
    check(not th.is_alive(), "the serve loop did not stop")
    return replies


def _serve_over_udp(model, argv: list[str], requests: list[np.ndarray]):
    """Build the server from ``argv`` as ``launch/serve.py`` does, serve
    ``requests`` through it, shut it down -> ``({tag: tokens}, stats)``."""
    from repro.launch import serve as S

    args = S.build_parser().parse_args(argv + ["--reply-port", "0"])
    gsys, controller, srv = S.make_server(args)
    try:
        def run(reply_port):
            args.reply_port = reply_port
            return S.serve(args, gsys, srv, model, controller=controller,
                           n_requests=len(requests))

        return _drive_server(gsys, srv, run, requests), srv.stats
    finally:
        srv.close()
        gsys.shutdown()


def _requests(rng, n: int, tag0: int, vocab: int) -> list[np.ndarray]:
    prompts = rng.integers(0, vocab, (n, PROMPT_LEN), dtype=np.int32)
    return [np.concatenate([[BUDGET, tag0 + i], p]).astype(np.int32)
            for i, p in enumerate(prompts)]


def _reference_tokens(model, lasts: list[int]) -> tuple[list[list[int]],
                                                       float, float]:
    """Greedy tokens from calling ``model.serve_step`` directly, with the
    batch shape the server uses for ``lasts`` (the last prompt tokens)
    and the same fresh cache. Returns (tokens per row, first-call seconds,
    steady seconds per step)."""
    import jax
    import jax.numpy as jnp

    from repro.models.registry import get_api

    cfg, k = model.cfg, len(lasts)
    cache = get_api(cfg).init_cache(cfg, k, 256)
    cur = jnp.asarray(np.asarray(lasts, np.int32).reshape(k, 1))
    cl = jnp.zeros((k,), jnp.int32)
    out: list[list[int]] = [[] for _ in range(k)]
    steps = []
    with model.mesh:
        for _ in range(BUDGET):
            t0 = time.perf_counter()
            nxt, cache = model.serve_step(model.params, cache, cur, cl)
            nxt_np = np.asarray(nxt)
            steps.append(time.perf_counter() - t0)
            for i in range(k):
                out[i].append(int(nxt_np[i]))
            cur = jnp.reshape(nxt, (k, 1))
            cl = cl + 1
    return out, steps[0], float(np.median(steps[1:]))


# ---------------------------------------------------------- (c) rwkv6-3b --

def phase_rwkv(cfg, seed: int) -> None:
    import jax

    from repro.launch import serve as S
    from repro.serving.server import _bucket_size

    t0 = time.perf_counter()
    model = S.load_model(cfg, seed=seed)
    jax.block_until_ready(model.params)
    init_s = time.perf_counter() - t0
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(model.params))
    log("rwkv6", f"{cfg.arch_id}: {cfg.n_layers} layers, d_model "
                 f"{cfg.d_model}, vocab {cfg.vocab_size}, {n_params} params "
                 f"in {model.cfg.params_dtype}, init {init_s:.1f}s "
                 f"(compile + run)")
    rng = np.random.default_rng(seed)
    eager = _requests(rng, EAGER_REQUESTS, 0, cfg.vocab_size)
    batched = _requests(rng, BATCH_REQUESTS, EAGER_REQUESTS, cfg.vocab_size)

    want: dict[int, list[int]] = {}
    timings = []
    for r in eager:
        toks, first, steady = _reference_tokens(model, [int(r[-1])])
        want[int(r[1])] = toks[0]
        timings.append((first, steady))
    log("rwkv6", f"direct serve_step, batch 1: first call "
                 f"{timings[0][0]:.2f}s (compile + step), steady "
                 f"{np.median([t[1] for t in timings]) * 1e3:.2f}ms/token")
    kb = _bucket_size(len(batched))
    lasts = [int(r[-1]) for r in batched] + [0] * (kb - len(batched))
    toks, first, steady = _reference_tokens(model, lasts)
    for r, t in zip(batched, toks):
        want[int(r[1])] = t
    log("rwkv6", f"direct serve_step, batch {kb}: first call {first:.2f}s "
                 f"(compile + step), steady {steady * 1e3:.2f}ms/step")

    for batch_decode, reqs in ((False, eager), (True, batched)):
        mode = "batch-decode" if batch_decode else "eager"
        got, stats = _serve_over_udp(model, [
            "--arch", cfg.arch_id, "--use-ring", "--per-request-tokens",
            "--max-tokens", str(BUDGET), "--batches", "1000"]
            + (["--batch-decode"] if batch_decode else []), reqs)
        tags = [int(r[1]) for r in reqs]
        check(sorted(got) == sorted(tags),
              f"{mode}: answered tags {sorted(got)}, sent {sorted(tags)}")
        for t in tags:
            check(got[t] == want[t],
                  f"{mode}: request {t} got {got[t]}, direct serve_step "
                  f"gives {want[t]}")
        per = stats.wall_s / max(1, stats.decode_dispatches)
        log("rwkv6", f"{mode}: {len(reqs)}/{len(reqs)} answered over the "
                     f"ring, tokens equal direct serve_step; "
                     f"{stats.tokens_out} tokens in {stats.wall_s:.2f}s, "
                     f"{stats.decode_dispatches} dispatches "
                     f"({per * 1e3:.2f}ms each)")


# ------------------------------------------------- (d) continuous engine --

def phase_continuous(cfg, seed: int, *, slots: int = 8, kv_blocks: int = 256,
                     block_size: int = 16) -> None:
    import jax

    from repro.launch import serve as S

    t0 = time.perf_counter()
    model = S.load_model(cfg, seed=seed)
    jax.block_until_ready(model.params)
    log("engine", f"{cfg.arch_id}: {cfg.n_layers} layers, d_model "
                  f"{cfg.d_model}, vocab {cfg.vocab_size}, init "
                  f"{time.perf_counter() - t0:.1f}s (compile + run)")
    reqs = _requests(np.random.default_rng(seed + 1), ENGINE_REQUESTS, 0,
                     cfg.vocab_size)
    got, stats = _serve_over_udp(model, [
        "--arch", cfg.arch_id, "--continuous", "--slots", str(slots),
        "--kv-blocks", str(kv_blocks), "--block-size", str(block_size),
        "--use-ring", "--per-request-tokens", "--max-tokens", str(BUDGET)],
        reqs)
    tags = [int(r[1]) for r in reqs]
    check(sorted(got) == sorted(tags),
          f"answered tags {sorted(got)}, sent {sorted(tags)}")
    for t in tags:
        check(len(got[t]) == BUDGET and
              all(0 <= v < cfg.padded_vocab for v in got[t]),
              f"request {t}: reply {got[t]} is not {BUDGET} token ids")
    log("engine", f"{len(reqs)}/{len(reqs)} answered, {BUDGET} tokens each; "
                  f"{stats.decode_dispatches} steps in {stats.wall_s:.2f}s "
                  f"(first step compiles)")


# ------------------------------------------------ --four-chips: training --

def train_losses(cfg, n_chips: int, seed: int, workdir: Path, *,
                 steps: int = TRAIN_STEPS, batch: int = 8,
                 seq: int = 128) -> list[float]:
    """Losses of ``steps`` data-parallel Trainer steps on the first
    ``n_chips`` devices, fed by the GENESYS loader from a seeded shard."""
    from repro.core.genesys import Genesys, GenesysConfig
    from repro.data.pipeline import write_token_shard
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_trainer

    shard = workdir / f"tokens-{seed}.bin"
    if not shard.exists():
        write_token_shard(str(shard), np.random.default_rng(seed).integers(
            0, cfg.vocab_size, size=batch * (seq + 1) * 64
        ).astype(np.uint32))
    gsys = Genesys(GenesysConfig(n_workers=2, coalesce_window_us=200,
                                 coalesce_max=8))
    mesh = make_host_mesh(data=n_chips)
    try:
        tr, loader = make_trainer(cfg, gsys, [str(shard)], mesh, batch=batch,
                                  seq=seq, seed=seed)
        try:
            with mesh:
                t0 = time.perf_counter()
                losses = tr.run(steps).losses
                wall = time.perf_counter() - t0
        finally:
            loader.close()
    finally:
        gsys.shutdown()
    log("train", f"{n_chips} chip(s): losses {losses}, {steps} steps in "
                 f"{wall:.1f}s (first step compiles)")
    return losses


def phase_four_chips(cfg, seed: int, workdir: Path, n_chips: int = 4
                     ) -> None:
    one = train_losses(cfg, 1, seed, workdir)
    gc.collect()
    many = train_losses(cfg, n_chips, seed, workdir)
    check(all(math.isfinite(x) for x in one + many), "a loss is not finite")
    diffs = [abs(a - b) for a, b in zip(one, many)]
    worst = max(diffs)
    log("train", f"largest |loss(1) - loss({n_chips})| = {worst!r} "
                 f"(per step {diffs})")
    check(all(d <= TRAIN_LOSS_RTOL * abs(a) for d, a in zip(diffs, one)),
          f"{n_chips}-chip losses leave {TRAIN_LOSS_RTOL:.0%} of 1-chip")


# -------------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel training comparison, "
                         "4 chips against 1")
    args = ap.parse_args(argv)

    info = phase_device(min_count=4 if args.four_chips else 1)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log("setup", f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        workdir = Path(tmp)
        if args.four_chips:
            cfg = replace(get_config("rwkv6-3b"), n_layers=TRAIN_LAYERS)
            phase_four_chips(cfg, args.seed, workdir)
        else:
            phase_syscalls(args.seed, workdir)
            phase_rwkv(get_config("rwkv6-3b"), args.seed)
            gc.collect()
            phase_continuous(replace(get_config("internlm2-20b"),
                                     n_layers=ENGINE_LAYERS), args.seed)
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
