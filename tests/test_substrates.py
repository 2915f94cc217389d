"""Data pipeline, checkpointing (crash consistency + elastic restore),
serving, compression, sharding helpers."""
import json
import os
import socket
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import CheckpointManager
from repro.data.pipeline import GenesysDataLoader, write_token_shard
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.optim.compression import compress_tree, decompress_tree
from repro.serving.server import CpuBaselineUdpServer, GenesysUdpServer
from repro.sharding import (ShardingRules, apply_fsdp, fit_spec, kv_repeat,
                            rules_for)
from proptest import for_all


# ------------------------------------------------------------ data ----------

def test_loader_reads_real_tokens(gsys, tmp_path):
    toks = np.arange(10_000, dtype=np.uint32)
    shard = str(tmp_path / "t.bin")
    write_token_shard(shard, toks)
    dl = GenesysDataLoader(gsys, [shard], batch=2, seq=16, prefetch_depth=2,
                           seed=1)
    b = dl.next_batch()
    assert b["tokens"].shape == (2, 16) and b["labels"].shape == (2, 16)
    # labels are tokens shifted by one (contiguous file ranges)
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()
    dl.close()


def test_loader_prefetch_depth(gsys, tmp_path):
    shard = str(tmp_path / "t.bin")
    write_token_shard(shard, np.zeros(50_000, dtype=np.uint32))
    dl = GenesysDataLoader(gsys, [shard], batch=1, seq=8, prefetch_depth=3)
    assert dl.stats["reads"] == 3          # issued ahead
    dl.next_batch()
    assert dl.stats["reads"] == 4
    dl.close()


# ------------------------------------------------------- checkpointing ------

def _tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"x": jnp.ones((5,), jnp.bfloat16),
                  "n": jnp.array(7, jnp.int32)}}


def test_checkpoint_roundtrip(gsys, tmp_path):
    cm = CheckpointManager(gsys, str(tmp_path), keep=2)
    t = _tree()
    cm.save(10, t)
    out = cm.restore(10, t)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_latest(gsys, tmp_path):
    cm = CheckpointManager(gsys, str(tmp_path), keep=2)
    for s in (1, 2, 3):
        cm.save(s, _tree())
    assert cm.list_steps() == [2, 3]
    assert cm.latest_step() == 3


def test_checkpoint_crash_consistency(gsys, tmp_path):
    """A step dir without a committed manifest is invisible."""
    cm = CheckpointManager(gsys, str(tmp_path), keep=3)
    cm.save(5, _tree())
    broken = tmp_path / "step_00000009"
    broken.mkdir()
    (broken / "leaf_00000.bin").write_bytes(b"partial garbage")
    assert cm.list_steps() == [5]          # uncommitted step ignored
    assert cm.latest_step() == 5


def test_checkpoint_elastic_resharding(gsys, tmp_path):
    """Restore under explicit (different) shardings — elastic restart."""
    cm = CheckpointManager(gsys, str(tmp_path))
    t = _tree()
    cm.save(1, t)
    mesh = make_mesh((1,), ("model",))
    sh = jax.tree_util.tree_map(
        lambda _: jax.sharding.NamedSharding(mesh, P()), t)
    out = cm.restore(1, t, shardings=sh)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- serving ------

def test_genesys_echo_server_roundtrip(gsys):
    srv = GenesysUdpServer(gsys, port=0, max_batch=4, payload=256)
    port = gsys.table._sockets[srv.fd].getsockname()[1]
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.bind(("127.0.0.1", 0))
    cport = client.getsockname()[1]
    client.settimeout(5)

    def run():
        srv.serve_echo(n_batches=1, reply_port=cport)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    client.sendto(b"hello-gpu-syscalls", ("127.0.0.1", port))
    data, _ = client.recvfrom(256)
    assert data == b"hello-gpu-syscalls"
    th.join(5)
    assert srv.stats.requests >= 1
    srv.close()
    client.close()


def test_cpu_baseline_server_roundtrip():
    srv = CpuBaselineUdpServer(port=0)
    port = srv.sock.getsockname()[1]
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.bind(("127.0.0.1", 0))
    cport = client.getsockname()[1]
    client.settimeout(5)
    th = threading.Thread(target=srv.serve_echo,
                          kwargs=dict(n_batches=1, reply_port=cport),
                          daemon=True)
    th.start()
    client.sendto(b"ping", ("127.0.0.1", port))
    assert client.recvfrom(64)[0] == b"ping"
    th.join(5)
    srv.close()
    client.close()


# ---------------------------------------------------------- compression -----

@for_all(n_cases=20)
def test_property_int8_ef_bounded_error(rng):
    g = {"a": jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)),
         "b": jnp.asarray(rng.normal(size=(7,)).astype(np.float32))}
    payload, err = compress_tree(g, "int8_ef")
    deq = decompress_tree(payload, "int8_ef")
    for k in g:
        q_err = np.abs(np.asarray(deq[k] - g[k]))
        scale = np.abs(np.asarray(g[k])).max() / 127.0 + 1e-12
        assert q_err.max() <= scale * 1.01
        # error feedback carries exactly the quantization residual
        np.testing.assert_allclose(np.asarray(err[k]),
                                   np.asarray(g[k] - deq[k]), atol=1e-6)


def test_bf16_compression_roundtrip():
    g = {"a": jnp.ones((4, 4)) * 1.5}
    payload, _ = compress_tree(g, "bf16")
    assert payload["a"].dtype == jnp.bfloat16
    out = decompress_tree(payload, "bf16")
    np.testing.assert_allclose(np.asarray(out["a"]), 1.5)


# ------------------------------------------------------------- sharding -----

def test_fit_spec_drops_nondivisible():
    mesh = make_host_mesh()
    # model axis size 1 -> kept as-is (harmless)
    assert fit_spec(P("model", None), (7, 3), mesh) == P("model", None)


def test_kv_repeat_rules():
    from repro.configs import get_config
    assert kv_repeat(get_config("qwen2-72b"), 16) == 2       # 8kv G8 -> 16
    assert kv_repeat(get_config("internlm2-20b"), 16) == 2   # 8kv G6 -> 16
    assert kv_repeat(get_config("starcoder2-7b"), 16) == 1   # G9 % 4 != 0
    assert kv_repeat(get_config("llava-next-34b"), 16) == 1  # G7 % 2 != 0
    assert kv_repeat(get_config("zamba2-2.7b"), 16) == 1     # kv32 >= 16


def test_apply_fsdp_picks_largest_free_dim():
    mesh = make_host_mesh()
    spec = apply_fsdp(P(None, "model", None), ("embed", "heads", "head_dim"),
                      (4096, 32, 128), mesh, ("data",))
    assert spec == P(("data",), "model", None)
