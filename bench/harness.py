"""One run of one cell: set-up, an open-loop window through the GENESYS
UDP server and the continuous engine, the check against the plain
reference, and the metrics the cell reports.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file, the architecture module that file names
(``bench/arch/<arch>.py``: sizes, weights, reference and counts), its
traffic mix ``bench/traffic/<mix>.json`` and one reader per metric,
``bench/metrics/<metric>.py``. The program is driven as a deployment
would drive it: ``launch/serve.make_server`` on the ring path,
``serving.engine.make_engine``, and
``GenesysUdpServer.serve_model_continuous``. Nothing here wraps,
donates or replaces the program's jitted step.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import schedule as SCH
import weights as W

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ARCH = BENCH / "arch"
STEP_MODULE = "paged_serve_step"      # the engine's jitted step, by name
CHUNK_MODULE = "paged_prefill_chunk"  # ... and its prefill chunk program
SNAPSHOT_EVERY_S = 4             # seconds between event-ring copies


class BenchError(RuntimeError):
    pass


# ------------------------------------------------------------- finding --

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    cfg: dict                 # the configuration file
    mix: dict                 # the traffic file
    end_to_end: list
    per_layer: list
    arch: object              # the module the configuration names


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bm: dict, name: str, arch_dir: Path = ARCH) -> Cell:
    """The workload ``name``, its files read and its architecture module
    loaded from ``arch_dir``."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = SCH.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), w["config"], cfg, mix,
                [m for m in bm["end_to_end"] if _applies(m, name)],
                [m for m in bm["per_layer"] if _applies(m, name)],
                load_arch(cfg, conf["file"], arch_dir))


def _load(path: Path, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module] = mod          # a dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return _load(BENCH / "metrics" / f"{metric}.py",
                 f"bench_metric_{metric}").read


def load_arch(cfg: dict, file: str, where: Path = ARCH):
    """The module ``<where>/<arch>.py`` that the configuration file
    ``file`` names under ``"arch"``."""
    name = cfg.get("arch")
    if name is None:
        raise BenchError(f"configuration {file} names no architecture: "
                         f"it needs \"arch\": \"<module in {where}>\"")
    path = where / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier()
            and path.is_file()):
        raise BenchError(f"configuration {file} names architecture "
                         f"{name!r}, and {where} has no module of that "
                         f"name")
    return _load(path, f"bench_arch_{name}")


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ----------------------------------------------------- the run's record --

@dataclass
class Run:
    """What a window left behind, for the metric readers."""
    cell: Cell
    dims: object                                   # cell.arch.Dims
    seconds: float
    setup_s: float = 0.0
    requests: list = field(default_factory=list)
    late_p50_ms: float | None = None
    late_max_ms: float | None = None
    engine0: dict = field(default_factory=dict)   # EngineStats at open
    engine1: dict = field(default_factory=dict)   # ... at close
    pool1: dict = field(default_factory=dict)     # PagedKVStats at close
    n_blocks: int = 0                              # the KV arena's blocks
    totals0: dict = field(default_factory=dict)   # telemetry totals
    totals1: dict = field(default_factory=dict)
    events: np.ndarray | None = None              # GENESYS trace events
    w0_ns: int = 0                                 # window, perf_counter
    w1_ns: int = 0
    trace: dict | None = None                      # tracefile.reduce
    window_s: float | None = None                  # traced window
    peaks: dict | None = None
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    # each second of the window: (t, requests queued for a slot, slots
    # busy, arena blocks in use)
    backlog: list = field(default_factory=list)


# ---------------------------------------------------------- the program --

def check_layout(mc, params) -> None:
    """The weights must have exactly the program's own tree and shapes."""
    import jax
    from repro.models.registry import get_api

    want = jax.eval_shape(lambda r: get_api(mc).init(r, mc)[0],
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise BenchError(f"weight layout differs from the program's: "
                         f"{got} != {want}")


@dataclass
class Model:
    mc: object
    mesh: object
    rules: object
    params: object


def build_model(cell: Cell, m, seed: int) -> Model:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import rules_for

    mc = cell.arch.program_config(cell.config_name, m)
    mesh = make_host_mesh()
    rules = rules_for(mc, mesh)
    params = W.make_program_params(cell.arch, m, seed)
    check_layout(mc, params)
    jax.block_until_ready(params)
    return Model(mc, mesh, rules, params)


def _stats_dict(stats) -> dict:
    return {k: getattr(stats, k) for k in stats.__dataclass_fields__}


class _CompileCounter:
    """Counts backend compilations while ``armed``."""

    def __init__(self):
        import jax
        self.armed = False
        self.n = 0

        def listen(event, duration, **kw):
            if self.armed and "backend_compile" in event:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def serve_window(cell: Cell, model: Model, m, sched: SCH.Schedule,
                 seconds: float, *, t_start: float, trace: bool,
                 workdir: Path, compiles: _CompileCounter,
                 held: dict, keep_trace: Path | None = None) -> "Run":
    """Serve ``sched`` through the program and record the window. The
    engine, GENESYS instance and server go into ``held`` as they are
    made; the caller frees them with :func:`shut_down`. A traced run
    leaves a slice of its device trace in ``keep_trace`` if given."""
    import jax
    from repro.launch import serve as S
    from repro.serving.engine import make_engine

    sv = cell.cfg["serving"]
    argv = ["--arch", cell.config_name, "--reply-port", "0", "--use-ring",
            "--continuous", "--per-request-tokens"]
    if trace:
        argv += ["--trace-out", str(workdir / "genesys_trace.json")]
    args = S.build_parser().parse_args(argv)
    gsys, _, srv = S.make_server(args)
    held.update(gsys=gsys, srv=srv)
    port = gsys.table._sockets[srv.fd].getsockname()[1]
    with model.mesh:
        engine = make_engine(
            model.mc, model.rules, model.params, n_slots=sv["n_slots"],
            n_blocks=sv["n_blocks"], block_size=sv["block_size"],
            max_blocks_per_seq=sv["max_blocks_per_seq"], gsys=gsys)
        held["engine"] = engine
        # warm the one fixed-shape step through the engine's own path: two
        # steps, since the first one's arenas are not yet the step's output
        engine.admit(np.zeros(1, np.int32), 2)
        engine.drain()
        jax.block_until_ready(engine.arenas)

    sched_path = workdir / "schedule.npz"
    sched.save(sched_path)
    out_path = workdir / "client.json"
    child = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py"), str(sched_path),
         str(port), str(out_path)],
        cwd=str(BENCH), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    try:
        client_port = int(child.stdout.readline().split()[1])
        cap = float(cell.mix["reply_cap_s"])
        idle_s = SCH.longest_gap_s(sched) + 10.0
        result: dict = {}

        def loop():
            try:
                with model.mesh:
                    result["stats"] = srv.serve_model_continuous(
                        engine, reply_port=client_port, n_requests=len(sched),
                        max_idle_polls=int(idle_s / 0.2) + 1,
                        per_request_tokens=True)
            except Exception as e:              # raised by the caller
                result["error"] = e

        run = Run(cell=cell, dims=m, seconds=seconds)
        server = threading.Thread(target=loop, name="bench-serve",
                                  daemon=True)
        tracer = gsys.tracer
        prof_dir = workdir / "profile"
        if trace:
            jax.profiler.start_trace(
                str(prof_dir), profiler_options=_profile_options())
            tp0 = time.monotonic()
        server.start()
        t0 = time.monotonic() + 0.2
        deadline = t0 + seconds + cap
        run.setup_s = t0 - t_start
        run.engine0 = _stats_dict(engine.stats)
        run.totals0 = dict(gsys.telemetry()["totals"])
        child.stdin.write(f"go {t0!r} {deadline!r}\n")
        child.stdin.flush()
        now_pc, now_mo = time.perf_counter_ns(), time.monotonic()
        run.w0_ns = now_pc + int((t0 - now_mo) * 1e9)
        run.w1_ns = run.w0_ns + int(seconds * 1e9)
        snaps = []
        _sleep_until(t0)
        compiles.n, compiles.armed = 0, True
        tick = 0
        while time.monotonic() < t0 + seconds:
            _sleep_until(min(t0 + seconds, t0 + tick + 1.0))
            tick += 1
            run.backlog.append((time.monotonic() - t0,
                                srv.stats.queue_depth, engine.n_active,
                                engine.pool.stats.blocks_in_use))
            if tracer is not None and (tick % SNAPSHOT_EVERY_S == 0
                                       or time.monotonic() >= t0 + seconds):
                snaps.append(tracer.events.snapshot())
        compiles.armed = False
        run.engine1 = _stats_dict(engine.stats)
        run.pool1 = _stats_dict(engine.pool.stats)
        run.n_blocks = int(sv["n_blocks"])
        run.totals1 = dict(gsys.telemetry()["totals"])
        if trace:
            run.window_s = time.monotonic() - tp0
            jax.profiler.stop_trace()
        run.compiles_in_window = compiles.n
        child.wait(timeout=deadline - time.monotonic() + 30)
        server.join(timeout=idle_s + 30)
        if "error" in result:
            raise result["error"]
        if tracer is not None:
            from spans import merge
            run.events = merge(snaps)
        with open(out_path) as f:
            client = json.load(f)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    run.late_p50_ms = client["late_p50_ms"]
    run.late_max_ms = client["late_max_ms"]
    for i in range(len(sched)):
        run.requests.append({
            "due_s": float(sched.due_s[i]), "sent_s": client["sent_s"][i],
            "recv_s": client["recv_s"][i],
            "prompt_len": int(sched.prompt_len[i]),
            "budget": int(sched.budget[i]),
            "tokens": client["tokens"][i]})
    if trace:
        import tracefile
        tr = tracefile.load_xplane(tracefile.find_xplane(str(prof_dir)))
        run.trace = tracefile.reduce(tr, STEP_MODULE,
                                     chunk_module=CHUNK_MODULE)
        if keep_trace is not None:
            keep_sample(tr, keep_trace)
    return run


def keep_sample(tr: dict, out_dir: Path, span_ns: float = 0.25e9) -> None:
    """A quarter second of the trace from the middle of the window, and
    the names of every plane's lines."""
    out_dir.mkdir(parents=True, exist_ok=True)
    starts = [s for _, s, _ in tr["ops"]]
    mid = float(np.median(starts)) if starts else 0.0
    part = {k: [e for e in tr[k] if mid <= e[1] < mid + span_ns]
            for k in ("ops", "modules", "host")}
    part["lines"] = tr["lines"]
    (out_dir / "trace_sample.json").write_text(json.dumps(part))


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def memory_peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def shut_down(held: dict) -> None:
    """Free the program's device state before the reference runs."""
    engine, gsys, srv = (held.pop(k, None) for k in ("engine", "gsys", "srv"))
    if engine is not None:
        engine.arenas = None
        engine.params = None
    if srv is not None:
        srv.close()
    if gsys is not None:
        gsys.shutdown()
    gc.collect()


# ---------------------------------------------------------------- check --

def finished(run: Run) -> list[int]:
    """Requests whose reply came with exactly the tokens asked for."""
    return [i for i, r in enumerate(run.requests)
            if r["recv_s"] is not None and r["tokens"] is not None
            and len(r["tokens"]) == r["budget"]]


def pick_sample(run: Run, seed: int, rows: int) -> list[int]:
    """The longest finished request and others drawn from the seed."""
    done = finished(run)
    if not done:
        return []
    longest = max(done, key=lambda i: run.requests[i]["prompt_len"]
                  + run.requests[i]["budget"])
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([int(seed) % 2**64, 0xC4EC])
    rng.shuffle(rest)
    return [longest] + rest[:rows - 1]


def check(run: Run, cell: Cell, sched: SCH.Schedule, seed: int, *,
          control: bool = False) -> dict:
    """Compare the served tokens of a sample with the reference. Returns
    the numbers compared, each with its limit, and the raw gaps."""
    import reference as R

    ck = cell.cfg["check"]
    sv = cell.cfg["serving"]
    positions = sv["max_blocks_per_seq"] * sv["block_size"]
    sample = pick_sample(run, seed, ck["sample_rows"])
    unanswered = sum(r["recv_s"] is None for r in run.requests)
    wrong_length = sum(r["recv_s"] is not None
                       and len(r["tokens"] or []) != r["budget"]
                       for r in run.requests)
    out = {"unanswered": {"value": unanswered, "limit": 0},
           "wrong_length": {"value": wrong_length, "limit": 0}}
    gaps = cgaps = np.zeros(0)
    if sample:
        ref = R.Reference(cell.arch, cell.cfg, seed, ck["sample_rows"],
                          positions)
        prompts = [sched.prompts[i] for i in sample]
        served = [run.requests[i]["tokens"] for i in sample]
        gaps = R.served_gaps(ref, prompts, served)
        if control:
            cgaps = R.served_gaps(ref, prompts, served, control=True)
    widest = float(gaps.max()) if len(gaps) else float("inf")
    out["widest_gap"] = {"value": widest, "limit": ck["widest_gap_limit"]}
    out["tokens_compared"] = {"value": int(len(gaps)),
                              "limit": ck["min_tokens_compared"]}
    res = {"numbers": out, "gaps": gaps, "sample": sample}
    if control:
        res["control_widest_gap"] = float(cgaps.max()) if len(cgaps) \
            else float("inf")
    return res


def is_correct(numbers: dict) -> bool:
    n = numbers
    return (n["unanswered"]["value"] <= 0 and n["wrong_length"]["value"] <= 0
            and n["widest_gap"]["value"] <= n["widest_gap"]["limit"]
            and n["tokens_compared"]["value"]
            >= n["tokens_compared"]["limit"])


# ---------------------------------------------------------------- a run --

def device_info(chips: int, *, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise BenchError(f"need {chips} TPU device(s); JAX found "
                         f"{info['count']} {info['platform']} device(s)")
    return info


def enable_cache() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def prepare(cell: Cell, *, tiny: bool = False):
    """The cell's model sizes, the device (checked) and its peaks."""
    sys.path.insert(0, str(ROOT / "src"))
    if tiny:
        shrink(cell)
    dev = device_info(cell.chips, require_tpu=not tiny)
    peaks = None if tiny else peaks_for(dev["kind"])
    enable_cache()
    return cell.arch.dims(cell.cfg), dev, peaks


def one_window(cell: Cell, m, seed: int, seconds: float, *,
               trace: bool, t_start: float, compiles: _CompileCounter,
               rate_per_s: float | None = None,
               control: bool = False,
               keep_trace: Path | None = None) -> tuple:
    """Make the weights, serve one window, free the program, check it
    against the reference -> ``(run, check result)``."""
    sched = SCH.make_schedule(cell.mix, seed, seconds, m.vocab,
                              rate_per_s=rate_per_s)
    workdir = Path(tempfile.mkdtemp(prefix="bench-run-"))
    held: dict = {}
    try:
        model = build_model(cell, m, seed)
        run = serve_window(cell, model, m, sched, seconds, t_start=t_start,
                           trace=trace, workdir=workdir, compiles=compiles,
                           held=held, keep_trace=keep_trace)
        run.memory_peak_bytes = memory_peak_bytes()
        del model
        shut_down(held)
        res = check(run, cell, sched, seed, control=control)
    finally:
        shut_down(held)
        shutil.rmtree(workdir, ignore_errors=True)
    return run, res


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, tiny: bool = False,
             keep_trace: Path | None = None) -> dict:
    """One run of ``cell``: the result line's object."""
    m, dev, peaks = prepare(cell, tiny=tiny)
    run, res = one_window(cell, m, seed, seconds, trace=trace,
                          t_start=t_start, compiles=_CompileCounter(),
                          keep_trace=keep_trace)
    run.peaks = peaks
    print(f"client: sent {run.late_p50_ms!r} ms late at the median, "
          f"{run.late_max_ms!r} ms at most; compiles in the window: "
          f"{run.compiles_in_window}", flush=True)
    return result_line(cell, run, res, dev, trace, tiny=tiny)


def result_line(cell: Cell, run: Run, res: dict, dev: dict, trace: bool,
                *, tiny: bool = False) -> dict:
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for mt in wanted:
        if tiny and mt["source"] == "device_trace":
            continue
        v = load_reader(mt["name"])(run)
        if v is not None:
            metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    device = dict(dev)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    line = {"correct": is_correct(res["numbers"]),
            "attempted": len(run.requests),
            "failed": sum(r["recv_s"] is None for r in run.requests),
            "metrics": metrics, "device": device}
    # how much of the KV arena the traffic reached (the null block aside)
    line["arena"] = {"blocks": run.n_blocks,
                     "in_use_at_close": run.pool1.get("blocks_in_use"),
                     "peak_in_use": run.pool1.get("peak_blocks_in_use")}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.window_s
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["check"] = res["numbers"]
    return line


# -------------------------------------------------- the CPU dry run size --

TINY_SERVING = {"n_slots": 4, "block_size": 16, "n_blocks": 48,
                "max_blocks_per_seq": 6}


def shrink(cell: Cell) -> None:
    """A tiny copy of the cell for a dry run on the CPU: the same path,
    the same traffic shape, sizes a test can hold."""
    cell.cfg = dict(cell.cfg, **cell.arch.TINY)
    cell.cfg["serving"] = dict(cell.cfg["serving"], **TINY_SERVING)
    cell.cfg["check"] = dict(cell.cfg["check"], sample_rows=4,
                             min_tokens_compared=1)
    mix = json.loads(json.dumps(cell.mix))
    mix["prompt"].update(median=12, min=2, max=48)
    mix["output"].update(median=6, min=2, max=40)
    mix["rate_per_s"] = 3.0
    mix["reply_cap_s"] = 60.0
    cell.mix = mix
