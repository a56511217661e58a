"""On-chip serving benchmark: one cell, one run, one JSON line.

    python3 benchmarks/chip/run.py --workload minicpm-2b.decode \\
        --seed 1234 --seconds 51 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>``:
the model as served, its batch, cache length and QuantSpec) and a traffic
mix (``traffic/<mix>.json``).  The configuration may name, as plain
module names of this directory, its plain reference (``"reference"``,
default ``reference.py``) and its work counts (``"work"``, default
``work.py``), so that a block other than the dense decoder comes with
files of its own.  A run:

1. refuses any platform but a TPU, and fewer chips than the cell asks;
2. keeps JAX's compilation cache at ``<checkout>/.jax_cache``;
3. builds the cell's ``ServeEngine`` (weights from ``--seed``, every dense
   weight planned into digit planes for the Pallas kernels);
4. warms up: the closed loop starts with staggered request lengths and
   runs a few steps, which compiles the one decode step;
5. drives ``ServeEngine.admit_from`` / ``step`` with an FCFS
   ``Scheduler`` for ``--seconds``, counting compilations (there should
   be none) and stamping every generated token on the host clock;
6. reads the device's peak memory, frees the engine, and compares a
   sample of the finished requests with the configuration's plain
   float32 reference: the widest gap by which a served token's
   reference logit lies below the reference's best;
7. prints the end-to-end metrics (``--trace 0``) or the per-layer ones
   (``--trace 1``: the first ``TRACE_SECONDS`` of the window are traced
   and each ``metrics/<name>.py`` reads the trace and the run's records).

The last line of stdout is the result; the numbers compared are the last
lines of stderr and the last key of the result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

WARMUP_STEPS = 4       # after the first (compiling) step
TRACE_SECONDS = 4.0    # of the window traced with --trace 1
SAMPLE_REQUESTS = 8    # finished requests the check compares
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
MODULE_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


# -- the cell, from files -----------------------------------------------------

def load_cell(name: str, root: str = ROOT, modules: str = HERE) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration file,
    traffic mix and the metrics it reports.  ``modules`` is the directory
    of the modules the configuration names (``module_path``)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    for key in ("reference", "work"):
        module_path(config, key, modules)
    return {"name": name, "chips": cell["chips"], "config": config,
            "mix": mix, "end_to_end": e2e, "per_layer": per_layer,
            "modules": modules}


def module_path(config: dict, key: str, directory: str) -> str:
    """The file of the module that the configuration's ``key`` names
    (``reference``, ``work``; where the key is absent, the module of
    that name): a plain name, no path, and a file ``<name>.py`` of
    ``directory``.  Anything else is refused."""
    name = config.get(key, key)
    path = os.path.join(directory, f"{name}.py")
    if not (isinstance(name, str) and MODULE_NAME.fullmatch(name)
            and os.path.isfile(path)):
        raise SystemExit(f"{config['name']}: {key} {name!r} is not a "
                         f"module of {directory}")
    return path


@functools.cache
def load_module(path: str):
    """The Python file ``path`` as a module, executed once a process."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict):
    """The program's ModelConfig for a configuration file.  Every size the
    file gives must match the model code's own published entry, apart
    from the keys the file lists as reduced and the dtypes it serves in."""
    from repro.configs.registry import get_config
    published = get_config(config["arch"])
    model = dict(config["model"])
    for key, value in model.items():
        if key in config["reduced"] or key in ("dtype", "param_dtype"):
            continue
        if getattr(published, key) != value:
            raise SystemExit(f"{config['name']}: {key}={value!r} but the "
                             f"model code publishes "
                             f"{getattr(published, key)!r}")
    return published.replace(**model)


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; exits unless they are
    at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"run.py measures on a TPU; JAX found platform "
                         f"{info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{info['count']}")
    return info


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- the serving loop ---------------------------------------------------------

class Loop:
    """Drives one engine with one scheduler and one traffic mix, recording
    each step (host times of its phases, slots bound, attended positions)
    and the host time at which each request's tokens arrived."""

    def __init__(self, eng, sched, traffic, clock, span):
        self.eng, self.sched, self.traffic = eng, sched, traffic
        self.clock, self.span = clock, span
        self.steps = []
        self.token_times = {}          # rid -> [host seconds per token]
        self.finished = []             # (host seconds, request)
        self.submitted = 0
        for req in traffic.initial(clock()):
            self.submit(req)

    def submit(self, req):
        self.sched.submit(req, req.arrival)
        self.submitted += 1

    def step(self):
        eng, slots = self.eng, self.eng.slots
        with self.span("bench.admit"):
            now = self.clock()
            eng.admit_from(self.sched, now)
            bound = slots.bound()
            before = [len(r.out) for _, r in bound]
            context = sum(int(slots.pos[i]) + 1 for i, _ in bound)
        with self.span("bench.step"):
            t0, c0 = self.clock(), time.thread_time()
            done = eng.step(t0)
            t1, c1 = self.clock(), time.thread_time()
        with self.span("bench.record"):
            for (_, req), n in zip(bound, before):
                if len(req.out) > n:
                    self.token_times.setdefault(req.rid, []).append(t1)
            for req in done:
                self.finished.append((t1, req))
                for nxt in self.traffic.finished(t1, req.rid):
                    self.submit(nxt)
            self.steps.append({"admit": now, "start": t0, "end": t1,
                               "step_cpu": c1 - c0, "bound": len(bound),
                               "context": context})


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(loop: Loop, t_start: float, t_end: float) -> dict:
    """Metrics of the window (t_start, t_end] from the token stamps."""
    tokens, gaps = 0, []
    for times in loop.token_times.values():
        tokens += sum(1 for t in times if t_start < t <= t_end)
        gaps.extend(b - a for a, b in zip(times, times[1:])
                    if t_start < a and b <= t_end)
    out = {"output_tok_s": tokens / (t_end - t_start)}
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    out["_counts"] = {"tokens": tokens, "gaps": len(gaps)}
    return out


def stall_report(steps, gc_pauses, t_start, top: int = 5) -> list:
    """Lines on the host's step-to-step gaps in the window: the median,
    and the ``top`` longest split into the time before admission (the
    last step's records and the loop), admission, the step call (wall
    and this thread's CPU) and garbage collection inside the gap."""
    gaps = [(b["end"] - a["end"], a, b) for a, b in zip(steps, steps[1:])]
    if not gaps:
        return []
    med = float(np.median([g for g, _, _ in gaps]))
    ms = lambda x: f"{1e3 * x:.1f}"  # noqa: E731
    out = [f"[run] step gaps: median {ms(med)} ms, "
           f"{sum(g > 1.5 * med for g, _, _ in gaps)} over 1.5x, lost "
           f"{ms(sum(max(g - med, 0.0) for g, _, _ in gaps))} ms; "
           f"{len(gc_pauses)} collections, "
           f"{ms(sum(d for _, d, _ in gc_pauses))} ms in all"]
    for g, a, b in sorted(gaps, key=lambda x: -x[0])[:top]:
        in_gc = [(d, gen) for s, d, gen in gc_pauses
                 if a["end"] <= s < b["end"]]
        out.append(
            f"[run]   at {b['start'] - t_start:.2f} s: gap {ms(g)} = "
            f"before admit {ms(b['admit'] - a['end'])} + admit "
            f"{ms(b['start'] - b['admit'])} + step {ms(b['end'] - b['start'])}"
            f" (cpu {ms(b['step_cpu'])}); gc "
            f"{[(ms(d), gen) for d, gen in in_gc]}")
    return out


# -- the correctness check ----------------------------------------------------

def sample_requests(finished, seed: int):
    """A sample, drawn from the seed, of SAMPLE_REQUESTS finished
    requests: the longest (prompt and output), and others at random."""
    reqs = sorted(finished, key=lambda r: r.rid)
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: (len(r.prompt) + len(r.out), -r.rid))
    rest = [r for r in reqs if r is not longest]
    order = np.random.default_rng([seed, 11]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:SAMPLE_REQUESTS - 1]]


def check(config: dict, seed: int, picked, control: bool,
          modules: str = HERE) -> dict:
    """Compare the served tokens of ``picked`` with the reference that
    the configuration names, a module of ``modules``."""
    reference = load_module(module_path(config, "reference", modules))
    spec = dict(kv.split("=") for kv in
                config["serve"]["quant_spec"].split(","))
    planes = int(spec["planes"])
    qmax = reference.plane_qmax(planes)
    # the control: the reference one digit plane coarser
    ctrl = reference.plane_qmax(planes - 1) if control else None
    seqs = [list(r.prompt) + list(r.out) for r in picked]
    starts = [len(r.prompt) for r in picked]
    while len(seqs) < SAMPLE_REQUESTS:          # same shapes every run
        seqs.append(seqs[0])
        starts.append(len(seqs[0]))             # no served tokens
    gaps = reference.logit_gaps(seed, config["model"], seqs, starts,
                                config["serve"]["max_len"], qmax, ctrl)
    out = {"max_logit_gap": float(gaps["served"].max()),
           "tokens": int(gaps["served"].size),
           "mismatched": int((gaps["served"] > 0).sum())}
    if control:
        out["control_max_logit_gap"] = float(gaps["control"].max())
        out["control_mismatched"] = int((gaps["control"] > 0).sum())
    return out


# -- one run ------------------------------------------------------------------

def load_metric(name: str):
    return load_module(os.path.join(HERE, "metrics", name + ".py"))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             control: bool = False, engine_hook=None, log=print) -> dict:
    """One run of ``cell``; returns the result record (without device).
    ``engine_hook(engine)`` runs once the engine is built (tests use it
    to break the timed path)."""
    import jax
    from repro.engine import QuantSpec
    from repro.serving import ServeEngine
    from repro.serving.request import ServeRequest
    from repro.serving.scheduler import Scheduler
    import profile_trace
    import traffic as traffic_lib

    clock = time.perf_counter
    config, serve = cell["config"], cell["config"]["serve"]
    work = load_module(module_path(config, "work", cell["modules"]))
    cfg = model_config(config)
    spec = QuantSpec.parse(serve["quant_spec"])
    t_engine = clock()
    eng = ServeEngine(cfg, serve["batch"], serve["max_len"], seed=seed,
                      quant=spec)
    engine_s = clock() - t_engine
    if engine_hook is not None:
        engine_hook(eng)
    sched = Scheduler("fcfs", max_len=serve["max_len"], on_too_long="error")
    mix = traffic_lib.Traffic(
        cell["mix"], seed, cfg.vocab_size, serve["max_len"],
        lambda rid, prompt, n, now: ServeRequest(rid, prompt, n,
                                                 arrival=now))
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    loop = Loop(eng, sched, mix, clock, span)
    t0 = clock()
    loop.step()                                   # compiles the step
    first_step_s = clock() - t0
    for _ in range(WARMUP_STEPS):
        loop.step()
    jax.block_until_ready(eng.state)
    setup_s = clock() - T_PROCESS
    warm = len(loop.steps)

    compiles = []

    def on_event(name, *args, **kw):
        if name in COMPILE_EVENTS:
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc_pauses = []        # (start, seconds, generation) in the window
    gc_start = 0.0

    def on_gc(phase, info):
        nonlocal gc_start
        if phase == "start":
            gc_start = clock()
        else:
            gc_pauses.append((gc_start, clock() - gc_start,
                              info["generation"]))
    gc.callbacks.append(on_gc)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    t_start = clock()
    deadline = t_start + seconds
    if trace:
        with span(profile_trace.WINDOW_SPAN):
            while clock() < min(deadline, t_start + TRACE_SECONDS):
                loop.step()
        jax.profiler.stop_trace()
    traced = len(loop.steps)
    while clock() < deadline:
        loop.step()
    t_end = loop.steps[-1]["end"]
    in_window = len(compiles)
    jax.monitoring.unregister_event_duration_listener(on_event)
    gc.callbacks.remove(on_gc)
    window_steps = loop.steps[warm:]

    memory_peak = None
    stats = jax.devices()[0].memory_stats()
    if stats:
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
    e2e = end_to_end(loop, t_start, t_end)
    finished = [r for t, r in loop.finished if t_start < t <= t_end]
    picked = sample_requests(finished, seed)
    attempted = loop.submitted
    failed = len(sched.rejected)
    del eng, sched, loop.eng, loop.sched
    gc.collect()

    summary = None
    if trace:
        summary = profile_trace.summarize(profile_trace.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_check = clock()
    result = check(config, seed, picked, control, cell["modules"]) \
        if picked else \
        {"max_logit_gap": None, "tokens": 0, "mismatched": 0}
    check_s = clock() - t_check
    limit = float(config["check"]["max_logit_gap"])
    correct = bool(picked) and failed == 0 and \
        result["max_logit_gap"] <= limit
    if control:
        # the control put in the program's place, through the same
        # comparison: it has to come out not correct
        result["control_correct"] = bool(picked) and \
            result["control_max_logit_gap"] <= limit

    log(f"[run] {cell['name']} seed {seed}: set-up {setup_s:.2f} s (engine "
        f"{engine_s:.2f} s, first step {first_step_s:.2f} s), window "
        f"{t_end - t_start:.2f} s, {len(window_steps)} steps, "
        f"{len(finished)} requests finished, compilations in window "
        f"{in_window}, check {check_s:.2f} s")
    log(f"[run] counts {e2e['_counts']}, memory peak {memory_peak}")
    log(f"[run] device memory {stats}")
    for line in stall_report(loop.steps[warm - 1:], gc_pauses, t_start):
        log(line)
    record = {"correct": correct, "attempted": attempted, "failed": failed,
              "setup_s": setup_s, "e2e": e2e, "check": result,
              "limit": limit, "memory_peak_bytes": memory_peak,
              "compiles_in_window": in_window,
              "window_steps": window_steps}
    if trace:
        run = types.SimpleNamespace(
            model=config["model"], serve=serve, bits=spec.bits,
            work=work, peaks=cell.get("peaks"), window_steps=window_steps,
            traced_steps=loop.steps[warm:traced], trace=summary)
        record["per_layer"] = {m["name"]: load_metric(m["name"]).read(run)
                               for m in cell["per_layer"]}
        record["trace"] = summary
    return record


def result_line(cell: dict, record: dict, info: dict, trace: bool) -> dict:
    """The contract's one JSON object; ``check`` comes last."""
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = record["per_layer"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(record["e2e"], setup_s=record["setup_s"])
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = dict(info, memory_peak_bytes=record["memory_peak_bytes"])
    line = {"correct": record["correct"], "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics,
            "device": device}
    summary = record.get("trace")
    if trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_by_host"]}
    c = record["check"]
    line["check"] = {"max_logit_gap": {"value": c["max_logit_gap"],
                                       "limit": record["limit"]}}
    if "control_correct" in c:
        line["check"]["control_max_logit_gap"] = {
            "value": c["control_max_logit_gap"], "limit": record["limit"],
            "correct": c["control_correct"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference one digit "
                         "plane coarser) and judge it by the same limit; "
                         "not part of a benchmark run")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    # JAX reads the cache directory when it is imported: fix it to the
    # checkout's own, whatever the environment holds
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    info = device_info(cell["chips"])
    cell["peaks"] = load_peaks(info["kind"])
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    record = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=bool(args.control), log=log)
    line = result_line(cell, record, info, bool(args.trace))
    c = record["check"]
    if args.control:
        log(f"control max_logit_gap {c['control_max_logit_gap']!r} limit "
            f"{record['limit']!r}: correct {c['control_correct']} "
            f"(mismatched {c['control_mismatched']} of {c['tokens']})")
    log(f"checked {c['tokens']} served tokens of {record['attempted']} "
        f"requests attempted; mismatched argmax {c['mismatched']}")
    log(f"max_logit_gap {c['max_logit_gap']!r} limit {record['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
