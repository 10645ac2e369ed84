#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything a cell needs is found by name from `BENCHMARK.json` at the root
of the checkout: its configuration file, its traffic mix
(`bench/traffic/<mix>.json`), the loop kind the mix names
(`bench/loops/<loop>.py`, which supplies the loop and its correctness
check), the limits of that check (`bench/limits/<cell>.json`) and, with
``--trace 1``, one reader per per-layer metric
(`bench/metrics/<metric>.py`). A new configuration, mix, loop kind or
metric is a new file, found by its name; no existing file changes.

A run: check that JAX sees a TPU and the cell's chips (otherwise exit 3
with no result); build the loop the mix names; warm up on one unit of
the cell's own traffic (set-up ends here); measure for ``--seconds``;
read the peak device memory; compare a sample of what the window produced
with the plain reference; print the provenance, then the result as the
last line of standard output, and the compared numbers with their limits
as the last lines of standard error. ``--trace 1`` records a profiler
trace of the window (the mix's ``trace_units`` units of work) and reports
the per-layer metrics instead of the end-to-end ones.

JAX's persistent compilation cache lives in `bench/.jax_cache` inside the
checkout, whatever the environment says, so that only a cell's first run
in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")


class Refused(Exception):
    """The run cannot give a result (exit code 2 or 3, no result line)."""

    def __init__(self, msg, code=2):
        super().__init__(msg)
        self.code = code


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, overrides=()):
    """(spec, cell, configuration, mix, limits) for a cell name."""
    spec_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(spec_path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", name + ".json"))
    for item in overrides:
        key, _, value = item.partition("=")
        where, *path = key.split(".")
        d = {"config": cfg, "mix": mix}[where]
        for p in path[:-1]:
            d = d[p]
        d[path[-1]] = json.loads(value)
    return spec, cell, cfg, mix, limits


def load_module(sub: str, name: str):
    """The module `bench/<sub>/<name>.py`, loaded once. Its directory goes
    on the path, so that a later file there can import it, and the helpers
    beside it, as plain modules."""
    where = os.path.join(BENCH, sub)
    if where not in sys.path:
        sys.path.insert(0, where)
    key = f"bench_{sub}_{name}"
    if key not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(
            key, os.path.join(where, name + ".py"))
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[key] = mod
        mod_spec.loader.exec_module(mod)
    return sys.modules[key]


def load_loop(name: str):
    """The loop class (`LOOP`) of the loop kind `bench/loops/<name>.py`."""
    return load_module("loops", name).LOOP


def load_reader(metric: str):
    """`read(run)` of `bench/metrics/<metric>.py`."""
    return load_module("metrics", metric).read


def sample_rng(seed: int):
    """The generator that draws a run's checked sample from its seed."""
    return np.random.default_rng([seed % (1 << 64), 1])


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


class CompileClock:
    """Programs JAX compiled (not loaded from the persistent cache), and the
    seconds it spent tracing, lowering, compiling or loading, process-wide."""

    def __init__(self, jax):
        self.seconds, self.backend, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def count(self) -> int:
        return self.backend - self.hits

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def device_info(jax, want_chips: int, allow_cpu: bool) -> dict:
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (default device {devs[0]}); this "
                      f"benchmark runs on the chip only", 3)
    if not allow_cpu and len(devs) < want_chips:
        raise Refused(f"the cell asks for {want_chips} chips, JAX found "
                      f"{len(devs)}", 3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory(jax) -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(args) -> tuple[dict, dict]:
    spec, cell, cfg, mix, limits = load_cell(args.workload, args.override)
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = device_info(jax, int(cell["chips"]), args.allow_cpu)
    import tracing
    import workmodel

    clock = CompileClock(jax)
    loop = load_loop(mix["loop"])(cfg, mix, args.seed)
    loop.warm_up()
    compiles_setup = clock.count
    setup_s = time.perf_counter() - T_START
    print(f"[bench] cell {args.workload}: device {device}; plan "
          f"{json.dumps(loop.plan)}; set-up {setup_s} s, "
          f"{compiles_setup} programs compiled, {clock.hits} loaded from "
          f"the cache, {clock.seconds} s tracing, compiling and loading",
          flush=True)

    trace_dir = None
    if args.trace:
        os.makedirs(TRACE_DIR, exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix="trace-", dir=TRACE_DIR)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the benchmark's spans suffice
        # XLA programs and ops only: the TPU's other trace lines fill its
        # trace buffer sooner
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        window_s = loop.run(args.seconds,
                            mix["trace_units"] if args.trace else None)
    if args.trace:
        jax.profiler.stop_trace()
    compiles_window = clock.count - compiles_setup
    counts = loop.counts()
    device["memory_peak_bytes"] = peak_memory(jax)
    print(f"[bench] window {window_s} s: {counts['units']} units, "
          f"{counts['attempted']} attempted, {counts['failed']} failed, "
          f"{compiles_window} compiles inside the window", flush=True)

    t_ref = time.perf_counter()
    numbers, info = loop.check(sample_rng(args.seed), mix["check_units"])
    brief = {k: info[k] for k in ("checked", "steps", "all_units")
             if k in info}
    print(f"[bench] reference: {brief} in {time.perf_counter() - t_ref} s",
          flush=True)

    wanted = cell_metrics(spec, args.workload, bool(args.trace))
    metrics = {}
    out = {"correct": None, "attempted": counts["attempted"],
           "failed": counts["failed"], "metrics": metrics, "device": device}
    if args.trace:
        devices, spans, cut = tracing.read_xplane(trace_dir)
        shutil.rmtree(trace_dir)
        win = [s for s in spans if s[0] == "bench.window"]
        lo, hi = win[-1][1], win[-1][2]
        # a device whose trace buffer ran out recorded only the start of the
        # window: reduce over that part, and count no lane-events in it
        truncated = cut is not None and cut < hi
        red = tracing.reduce(devices, spans, lo, min(hi, cut) if truncated
                             else hi)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
        events = None if truncated else loop.lane_events(info)
        ctx = {"trace": red, "lane_events": events, "loop": loop,
               "cfg": cfg, "device_kind": device["kind"],
               "workmodel": workmodel}
        print(f"[bench] trace: {red['n_devices']} devices, busy "
              f"{red['busy_s']} s of {red['window_s']} s"
              f"{' (cut short: trace buffer full)' if truncated else ''}, "
              f"longest gap {red['longest_gap_s']} s, lane-events {events}",
              flush=True)
        for m in wanted:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(loop.end_to_end(window_s), setup_s=setup_s)
        for m in wanted:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the cell's limits file names the numbers it compares; any other
    # number the check computes is provenance only
    print(f"[bench] numbers: {json.dumps(numbers)}", flush=True)
    checks = {k: {"value": numbers[k], "limit": lim["limit"]}
              for k, lim in limits.items()}
    out["correct"] = bool(counts["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    out["checks"] = checks
    return out, checks


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # rehearsal only: run without a chip, with fields of the configuration
    # or mix replaced (e.g. config.flows.n_jobs=300)
    ap.add_argument("--allow-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--override", action="append", default=[],
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        out, checks = run(args)
    except Refused as e:
        print(f"[bench] {e}", file=sys.stderr)
        return e.code
    print(json.dumps(out), flush=True)
    print(f"[bench] correct: {out['correct']}", file=sys.stderr)
    for k, c in checks.items():
        print(f"[bench] check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
