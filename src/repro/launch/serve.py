"""Serving launcher: batched UDP decode server over GENESYS network
syscalls (paper §7.3, generalized to a model server).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
      --port 9111 --batches 4

``--use-ring`` routes the decode loop's recvfrom/sendto through the
genesys.uring rings end-to-end; ``--tenants`` additionally runs it on
genesys.sched per-tenant rings (a high-priority receive tenant plus a
bounded pool of hash-sharded reply tenants) with token-bucket +
strict-priority + WFQ policies installed; ``--batch-decode`` decodes each
poll batch as one power-of-two bucket — one jit dispatch per token step
for the whole bucket, replies fanned out as one multi-entry submission.
"""
from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass, replace
from typing import Callable

import jax
from jax.sharding import Mesh

from repro.config import ModelConfig
from repro.sharding import ShardingRules


def start_stats_reporter(gsys, interval_s: float, *, out=print
                         ) -> tuple[threading.Thread, threading.Event]:
    """Start the ``--stats-interval`` reporter: a daemon thread printing
    one :func:`~repro.core.genesys.trace.format_summary` line (rates from
    consecutive telemetry snapshots) every ``interval_s`` seconds via
    ``out``. Returns ``(thread, stop_event)``; set the event and join the
    thread for a clean shutdown."""
    from repro.core.genesys import format_summary

    stop = threading.Event()

    def _report() -> None:
        prev, prev_t = None, time.monotonic()
        while not stop.wait(interval_s):
            snap = gsys.telemetry()
            now = time.monotonic()
            out(format_summary(snap, prev, now - prev_t))
            prev, prev_t = snap, now

    th = threading.Thread(target=_report, daemon=True, name="serve-stats")
    th.start()
    return th, stop


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--reply-port", type=int, required=True)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--use-ring", action="store_true",
                    help="decode-loop syscalls via the genesys.uring rings")
    ap.add_argument("--tenants", action="store_true",
                    help="per-tenant rings + QoS policies (implies --use-ring)")
    ap.add_argument("--batch-decode", action="store_true",
                    help="bucket concurrent requests: one jit dispatch per "
                         "token step per bucket (amortized decode)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the genesys.pagedkv "
                         "paged KV pool: fixed-shape slot-masked decode, "
                         "requests admitted/retired mid-decode")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots for --continuous")
    ap.add_argument("--kv-blocks", type=int, default=256,
                    help="paged KV arena blocks for --continuous")
    ap.add_argument("--block-size", type=int, default=16,
                    help="token positions per KV block for --continuous")
    ap.add_argument("--spill", default=None, metavar="PATH",
                    help="spill file for evicted prefix blocks "
                         "(PWRITE64 out, PREAD64_FIXED back)")
    ap.add_argument("--per-request-tokens", action="store_true",
                    help="wire format [budget, tag, prompt...]: each "
                         "request carries its own token budget")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable genesys.trace lifecycle telemetry and "
                         "write a Chrome-trace/Perfetto JSON here on exit")
    ap.add_argument("--stats-interval", type=float, default=0.0, metavar="N",
                    help="print a one-line telemetry summary (throughput, "
                         "per-tenant p99, fuse ratio) every N seconds")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the genesys.metrics Prometheus exposition "
                         "over TCP: GET /metrics scrapes, GET /telemetry "
                         "returns the full JSON snapshot (0 = ephemeral)")
    ap.add_argument("--slo-us", type=float, default=None, metavar="US",
                    help="declare a per-request latency SLO (µs) over the "
                         "serving wall-time histogram; burn-rate gauges "
                         "are derived every metrics tick")
    ap.add_argument("--slo-target", type=float, default=0.999,
                    help="fraction of requests that must meet --slo-us")
    ap.add_argument("--admit", action="store_true",
                    help="SLO-driven admission control: classify requests "
                         "into --slo-class groups, shed/degrade under burn "
                         "(shed replies carry SHED_TOKEN)")
    ap.add_argument("--slo-class", action="append", default=[],
                    metavar="NAME:SLO_US[:TARGET[:RANK]]",
                    help="declare an admission class (repeatable); RANK 0 "
                         "(default) is protected — degraded, never shed; "
                         "higher ranks shed first")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection: "
                         "'SEED[;TENANT:SYSNO:ERRNO:RATE]...' with '*' "
                         "wildcards (e.g. '7;*:45:EAGAIN:0.01')")
    return ap


def make_server(args):
    """The Genesys instance with the QoS policies, fault plan and
    admission controller ``args`` ask for, and the UDP server bound on
    ``--port`` -> ``(gsys, controller, srv)``."""
    from repro.core.genesys import (Genesys, GenesysConfig, StrictPriority,
                                    TokenBucket, WeightedFair)
    from repro.serving.server import GenesysUdpServer

    gsys = Genesys(GenesysConfig(n_workers=2, sched_pollers=2,
                                 trace=args.trace_out is not None))
    if args.tenants:
        gsys.use_policies(TokenBucket(), StrictPriority(), WeightedFair())
    if args.fault_plan:
        from repro.core.genesys import FaultPlan
        plan = gsys.use_fault_plan(FaultPlan.parse(args.fault_plan))
        print(f"fault plan installed: seed={plan.seed} "
              f"rules={len(plan._rules)}", flush=True)
    controller = None
    if args.admit:
        from repro.core.genesys import AdmissionController
        controller = AdmissionController(gsys.metrics)
        classes = []
        for spec in (args.slo_class or ["default:50000"]):
            parts = spec.split(":")
            name = parts[0]
            slo = float(parts[1]) if len(parts) > 1 else None
            target = float(parts[2]) if len(parts) > 2 else 0.999
            rank = int(parts[3]) if len(parts) > 3 else 0
            classes.append(controller.declare(
                name, slo_us=slo, target=target, priority_class=rank))
        # clients hash into classes by id; a custom mapper can replace this
        controller.map_default(
            lambda cid, _c=classes: _c[int(cid) % len(_c)].name)
        controller.install(gsys)
        print(f"admission control on: "
              f"{', '.join(c.name for c in classes)}", flush=True)
    srv = GenesysUdpServer(gsys, port=args.port, use_ring=args.use_ring,
                           use_tenants=args.tenants, admission=controller)
    return gsys, controller, srv


@dataclass
class ServingModel:
    cfg: ModelConfig          # params_dtype == compute_dtype
    mesh: Mesh
    rules: ShardingRules
    params: dict
    serve_step: Callable      # jitted one-token decode step


def serving_config(cfg: ModelConfig) -> ModelConfig:
    """Serving keeps its weights in the compute dtype (bf16): an f32 copy
    of rwkv6-3b alone is 12.4 GB, and a step that casts f32 weights makes
    another 5 GB of bf16 copies on every call."""
    return replace(cfg, params_dtype=cfg.compute_dtype)


def load_model(cfg: ModelConfig, seed: int = 0) -> ServingModel:
    """Serving weights for ``cfg``, initialised directly in the compute
    dtype, and its jitted decode step."""
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import init_params
    from repro.sharding import rules_for
    from repro.train.steps import make_serve_step

    cfg = serving_config(cfg)
    mesh = make_host_mesh()
    rules = rules_for(cfg, mesh)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    return ServingModel(cfg, mesh, rules, params,
                        jax.jit(make_serve_step(cfg, rules)))


def serve(args, gsys, srv, model: ServingModel, *, controller=None,
          n_requests: int | None = None):
    """Run the decode loop ``args`` selects (eager, ``--batch-decode``
    buckets or the ``--continuous`` engine) on ``srv`` until ``--batches``
    poll batches, or ``n_requests`` requests, are served."""
    cfg = model.cfg
    with model.mesh:
        if args.continuous:
            from repro.serving.engine import make_engine
            engine = make_engine(
                cfg, model.rules, model.params, n_slots=args.slots,
                n_blocks=args.kv_blocks, block_size=args.block_size,
                gsys=gsys, spill_path=args.spill)
            engine.admission = controller
            stats = srv.serve_model_continuous(
                engine, reply_port=args.reply_port,
                n_requests=n_requests, max_tokens=args.max_tokens,
                per_request_tokens=args.per_request_tokens)
            print(f"engine: occupancy={engine.stats.occupancy():.2f} "
                  f"prefill_saved={engine.stats.prefill_steps_saved} "
                  f"kv_hit_rate={engine.pool.stats.hit_rate():.2f} "
                  f"kv_rss={engine.pool.rss_bytes()}")
        else:
            from repro.models.registry import get_api
            cache = get_api(cfg).init_cache(cfg, 1, 256)
            stats = srv.serve_model(
                model.serve_step, model.params, cache,
                n_batches=args.batches, n_requests=n_requests,
                reply_port=args.reply_port, max_tokens=args.max_tokens,
                batch_decode=args.batch_decode,
                per_request_tokens=args.per_request_tokens)
    return stats


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    from repro.configs import get_config
    from repro.core.genesys import format_summary
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    gsys, controller, srv = make_server(args)
    reporter = stop_stats = None
    if args.stats_interval > 0:
        reporter, stop_stats = start_stats_reporter(
            gsys, args.stats_interval,
            out=lambda line: print(line, flush=True))
    metrics_srv = None
    if args.metrics_port is not None:
        from repro.core.genesys.metrics import MetricsHttpServer
        if args.slo_us is not None:
            gsys.metrics.set_slo("genesys_request_wall_us", args.slo_us,
                                 target=args.slo_target)
        metrics_srv = MetricsHttpServer(gsys.metrics,
                                        port=args.metrics_port,
                                        telemetry_fn=gsys.telemetry)
        print(f"metrics exposition on :{metrics_srv.port} "
              f"(/metrics, /telemetry)", flush=True)
    model = load_model(cfg)
    stats = serve(args, gsys, srv, model, controller=controller)
    print(f"requests={stats.requests} batches={stats.batches} "
          f"tokens={stats.tokens_out} wall={stats.wall_s:.2f}s "
          f"decode_dispatches={stats.decode_dispatches} "
          f"decode_steps={stats.decode_steps}")
    if args.tenants:
        for name, t in sorted(gsys.tenants().items()):
            print(f"tenant {name}: submitted={t.stats.submitted} "
                  f"reaped={t.stats.reaped} throttled={t.stats.throttled}")
    if controller is not None:
        a = controller.counters.snapshot()
        print(f"admit: admitted={a['admitted']} degraded={a['degraded']} "
              f"shed={a['shed']} level={a['shed_level']:.2f}")
    if reporter is not None:
        stop_stats.set()
        reporter.join(timeout=2)
        print(format_summary(gsys.telemetry()), flush=True)
    if metrics_srv is not None:
        metrics_srv.close()
    srv.close()
    if args.trace_out:
        gsys.export_chrome_trace(args.trace_out)
        print(f"chrome trace written to {args.trace_out}", flush=True)
    gsys.shutdown()


if __name__ == "__main__":
    main()
