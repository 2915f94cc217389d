"""End-to-end system tests: GENESYS-serviced training with checkpoint/
restart, HLO cost model sanity, the dry-run plumbing on a host mesh, and
the UDP model-serving loops (eager, bucketed and continuous)."""
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def test_end_to_end_training_with_genesys_services(gsys, tmp_path, mesh11):
    """Loader (pread prefetch) -> train steps -> async ckpt -> crash ->
    elastic resume -> loss finite & decreasing-ish."""
    from repro.checkpoint.ckpt import CheckpointManager
    from repro.config import TrainConfig
    from repro.configs import get_config
    from repro.data.pipeline import GenesysDataLoader, write_token_shard
    from repro.models.registry import get_api
    from repro.sharding import rules_for
    from repro.train.loop import Trainer
    from repro.train.steps import make_train_step

    shard = str(tmp_path / "tok.bin")
    write_token_shard(shard, np.random.default_rng(0).integers(
        0, 500, size=300_000).astype(np.uint32))
    cfg = get_config("internlm2-20b").reduced()
    rules = rules_for(cfg, mesh11)
    api = get_api(cfg)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    ts, opt = make_train_step(cfg, rules, TrainConfig(lr=3e-3))
    loader = GenesysDataLoader(gsys, [shard], batch=4, seq=32)
    cm = CheckpointManager(gsys, str(tmp_path / "ckpt"), keep=2)
    with mesh11:
        tr = Trainer(gsys, jax.jit(ts), params, opt.init(params), loader,
                     ckpt=cm, ckpt_every=16)
        # 32 steps: enough for the learning signal (unigram stats of the
        # random stream) to beat per-batch sampling noise on this setup
        st = tr.run(32)
        assert st.steps == 32 and st.ckpts == 2
        assert all(np.isfinite(l) for l in st.losses)
        assert np.mean(st.losses[-3:]) < np.mean(st.losses[:3])

        # simulated crash: fresh trainer resumes from the committed step
        tr2 = Trainer(gsys, jax.jit(ts), params, opt.init(params), loader,
                      ckpt=cm)
        assert tr2.resume()
        assert tr2.step == 32
        st2 = tr2.run(2)
        assert all(np.isfinite(l) for l in st2.losses)
    loader.close()


def test_microbatched_train_step_matches_single(mesh11):
    """Gradient accumulation must be loss-equivalent to the full batch."""
    from repro.config import TrainConfig
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.sharding import rules_for
    from repro.train.steps import make_train_step

    cfg = get_config("starcoder2-7b").reduced()
    rules = rules_for(cfg, mesh11)
    api = get_api(cfg)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, 100),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16),
                                          0, 100)}
    with mesh11:
        ts1, opt = make_train_step(cfg, rules, TrainConfig(microbatches=1))
        ts4, _ = make_train_step(cfg, rules, TrainConfig(microbatches=4))
        p1, _, m1 = jax.jit(ts1)(params, opt.init(params), batch)
        p4, _, m4 = jax.jit(ts4)(params, opt.init(params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 2e-3
    l1 = jax.tree_util.tree_leaves(p1)
    l4 = jax.tree_util.tree_leaves(p4)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(l1, l4))
    assert err < 5e-3, err


def test_hlo_cost_counts_loop_trips():
    from repro.perf.hlo_cost import analyze

    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    co = jax.jit(jax.grad(f)).lower(ws, x).compile()
    hc = analyze(co.as_text())
    # fwd dot + bwd dx dot + bwd dw dot, each 7 times
    assert hc.flops == 2 * 8 * 64 * 64 * 7 * 3
    assert hc.unknown_trip_loops == 0


def test_dryrun_cell_in_subprocess():
    """One full dry-run cell on the 512-device multi-pod mesh, in a
    subprocess so the device-count flag never leaks into this process."""
    code = (
        "from repro.launch.dryrun import run_cell\n"
        "out = run_cell('seamless-m4t-medium', 'decode_32k', True)\n"
        "assert out['status'] == 'ok', out\n"
        "assert out['chips'] == 512\n"
        "assert out['roofline']['bottleneck'] in "
        "('compute', 'memory', 'collective')\n"
        "print('CELL_OK')\n"
    )
    env = dict(os.environ,
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "CELL_OK" in r.stdout, r.stdout + r.stderr


def test_production_mesh_shapes():
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=512'\n"
        "from repro.launch.mesh import make_production_mesh\n"
        "m1 = make_production_mesh()\n"
        "m2 = make_production_mesh(multi_pod=True)\n"
        "assert dict(m1.shape) == {'data': 16, 'model': 16}\n"
        "assert dict(m2.shape) == {'pod': 2, 'data': 16, 'model': 16}\n"
        "print('MESH_OK')\n"
    )
    env = dict(os.environ,
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert "MESH_OK" in r.stdout, r.stdout + r.stderr


def test_compressed_crosspod_reduce_multidevice():
    """Distributed-optimization trick end-to-end on 8 host devices:
    int8+error-feedback compressed gradients survive a cross-pod psum with
    bounded error (shard_map over a (pod, data) mesh)."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "import jax, jax.numpy as jnp, numpy as np\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from repro.launch.mesh import make_mesh\n"
        "from repro.optim.compression import compress_tree, decompress_tree\n"
        "mesh = make_mesh((2, 4), ('pod', 'data'))\n"
        "def reduce_fn(g):\n"
        "    payload, _ = compress_tree({'g': g}, 'bf16')\n"
        "    summed = jax.lax.psum(payload['g'], ('pod', 'data'))\n"
        "    return decompress_tree({'g': summed}, 'bf16')['g']\n"
        "g = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100\n"
        "out = jax.jit(jax.shard_map(reduce_fn, mesh=mesh,\n"
        "    in_specs=P(('pod', 'data')), out_specs=P(('pod', 'data'))))(g)\n"
        "ref = jnp.broadcast_to(g.sum(0, keepdims=True), g.shape)\n"
        "err = float(jnp.max(jnp.abs(out - ref)))\n"
        "assert err < 0.2, err  # 8 shards x bf16 ulp(5.12)/2\n"
        "print('COMPRESS_REDUCE_OK', err)\n"
    )
    env = dict(os.environ,
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "COMPRESS_REDUCE_OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------ UDP model-serving loop ----

def _fake_serve_fn(params, cache, cur, cl):
    """Deterministic decode stub: next token = 2*cur + 1 (cache ignored),
    so any path's continuation is checkable without a model compile."""
    return cur.reshape(-1) * 2 + 1, cache


def _fake_paged_step(params, arenas, bt, cur, cl):
    return cur[:, 0] * 2 + 1, arenas


def _fake_prefill_chunk(params, arenas, row, tokens, start, n_valid):
    return arenas


def _chain(last, n):
    out = []
    for _ in range(n):
        last = 2 * last + 1
        out.append(last)
    return out


def _serve_requests(gsys, srv, serve, reqs, *, n_replies):
    """Run ``serve(reply_port)`` on a daemon thread, fire each int32
    request at the server, collect ``n_replies`` datagrams, and assert
    the serve loop actually terminated."""
    port = gsys.table._sockets[srv.fd].getsockname()[1]
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.bind(("127.0.0.1", 0))
    client.settimeout(10)
    th = threading.Thread(target=lambda: serve(client.getsockname()[1]),
                          daemon=True)
    th.start()
    time.sleep(0.05)
    for r in reqs:
        client.sendto(np.asarray(r, np.int32).tobytes(), ("127.0.0.1", port))
    replies = []
    try:
        for _ in range(n_replies):
            data, _ = client.recvfrom(4096)
            replies.append(np.frombuffer(data, np.int32).tolist())
    finally:
        client.close()
    th.join(20)
    assert not th.is_alive()       # the loop's stop conditions fired
    return replies


def test_serve_model_mixed_prompt_lengths_one_bucket(gsys):
    """One poll batch with three different prompt lengths AND budgets:
    the bucketed decode answers each tag with its own continuation, in a
    single bucket whose dispatch count is its longest member's budget."""
    from repro.serving.server import GenesysUdpServer
    cache = {"k": jnp.zeros((1, 1), jnp.float32)}
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.2, use_ring=True)
    reqs = [[2, 101, 7],            # [budget, tag, prompt...]
            [3, 102, 5, 9],
            [1, 103, 1, 2, 3, 4]]
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model(_fake_serve_fn, {}, cache, n_batches=1,
                                   reply_port=rp, max_tokens=8,
                                   batch_decode=True,
                                   per_request_tokens=True),
        reqs, n_replies=3)
    got = {r[0]: r[1:] for r in replies}
    assert got == {101: _chain(7, 2), 102: _chain(9, 3), 103: _chain(4, 1)}
    assert srv.stats.decode_buckets == 1
    assert srv.stats.decode_dispatches == 3    # longest budget bounds it
    assert srv.stats.decode_steps == 2 + 3 + 1
    srv.close()


def test_serve_model_idle_poll_termination(gsys):
    """A lost datagram must not strand the loop: with ``n_requests``
    unmet, ``max_idle_polls`` consecutive empty polls end the serve."""
    from repro.serving.server import GenesysUdpServer
    cache = {"k": jnp.zeros((1, 1), jnp.float32)}
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.02)
    gsys.table._sockets[srv.fd].settimeout(0.05)   # cheap idle polls
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model(_fake_serve_fn, {}, cache, n_batches=50,
                                   reply_port=rp, max_tokens=8,
                                   n_requests=2, max_idle_polls=3,
                                   per_request_tokens=True),
        [[2, 7, 11]], n_replies=1)                 # one of the two arrives
    assert replies == [[7] + _chain(11, 2)]
    assert srv.stats.requests == 1                 # exited via idle polls
    srv.close()


def test_serve_model_batch_matches_eager_per_request_budgets(gsys):
    """batch_decode=True with per-request budgets answers every tag with
    exactly the eager path's tokens — in max(budget) dispatches instead
    of sum(budget)."""
    from repro.serving.server import GenesysUdpServer
    cache = {"k": jnp.zeros((1, 1), jnp.float32)}
    reqs = [[4, 1, 3], [2, 2, 5, 6], [3, 3, 2]]
    out = {}
    for batch in (False, True):
        srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                               batch_window_s=0.2, use_ring=True)
        replies = _serve_requests(
            gsys, srv,
            lambda rp, s=srv, b=batch: s.serve_model(
                _fake_serve_fn, {}, cache, n_batches=1, reply_port=rp,
                max_tokens=8, batch_decode=b, per_request_tokens=True),
            reqs, n_replies=3)
        out[batch] = ({tuple(r) for r in replies},
                      srv.stats.decode_dispatches)
        srv.close()
    assert out[True][0] == out[False][0]
    assert out[False][1] == 4 + 2 + 3      # one dispatch per token step
    assert out[True][1] == 4               # longest member bounds the bucket


def test_serve_continuous_udp_end_to_end(gsys):
    """serve_model_continuous over UDP with a stub engine: a short
    request admitted mid-decode overtakes a long one (tags correlate the
    out-of-order completions), occupancy reflects the overlap, and the
    loop exits via idle polls when traffic dies short of n_requests."""
    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.pagedkv import PagedKVPool
    from repro.serving.server import GenesysUdpServer
    NB, BS = 8, 4
    arenas = {"k": jnp.zeros((1, NB, BS, 1, 1)),
              "v": jnp.zeros((1, NB, BS, 1, 1))}
    eng = ContinuousBatchEngine(_fake_paged_step, {}, arenas,
                                PagedKVPool(NB, BS),
                                prefill_chunk=_fake_prefill_chunk, n_slots=2,
                                max_blocks_per_seq=4)
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256,
                           batch_window_s=0.02, use_ring=True)
    gsys.table._sockets[srv.fd].settimeout(0.05)
    reqs = [[6, 900, 3],       # long budget: admitted first, finishes last
            [1, 901, 2, 4]]    # short: retires mid-decode of the long one
    replies = _serve_requests(
        gsys, srv,
        lambda rp: srv.serve_model_continuous(eng, reply_port=rp,
                                              n_requests=3,
                                              max_idle_polls=3),
        reqs, n_replies=2)
    got = {r[0]: r[1:] for r in replies}
    assert got == {900: _chain(3, 6), 901: _chain(4, 1)}
    assert replies[0][0] == 901            # overtook the in-flight request
    assert eng.stats.admitted == 2 and eng.stats.retired == 2
    assert eng.stats.occupancy() > 1.0
    assert eng.pool.stats.blocks_in_use == 0
    assert srv.stats.decode_steps > srv.stats.decode_dispatches
    srv.close()
