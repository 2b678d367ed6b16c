"""One run of one cell of BENCHMARK.json:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON line at the end of standard output.  The
harness is driven by data: the cell names a configuration (a file of sizes
under ``configs/``) and a traffic mix (a file of parameters under
``traffic/``); the traffic file names the window kind (a module under
``windows/``); each per-layer metric of BENCHMARK.json has a reader of its
own under ``metrics/``.  See README.md beside this file.

Without a TPU (or with fewer chips than the cell asks for) it exits with
code 2 and prints no result.  ``--dry`` rehearses the whole path on the CPU
at the configuration's ``dry`` sizes (Pallas in interpret mode) and prints
a line of another shape, which no check can take for a result.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import resource
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

TRACE_DIR = os.path.join(_ROOT, ".bench_trace")


def process_age_s() -> float:
    """Seconds since this process was started, from /proc (the interpreter's
    own start-up included); 0 where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_T_PROCESS = time.perf_counter() - process_age_s()


def load_module(kind: str, name: str, roots):
    """``<root>/<kind>/<name>.py`` from the first root that has it."""
    for root in roots:
        path = os.path.join(root, kind, name + ".py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmarks_{kind}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {list(roots)}")


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Run:
    """What one run knows; handed to the window kind and to each reader."""

    def __init__(self, args, cfg, traffic):
        self.cfg, self.traffic = cfg, traffic
        self.seed = int(args.seed)
        self.dry = bool(args.dry)
        self.control = bool(args.control)
        self.spans: dict[str, float] = {}     # set-up span name -> seconds
        self.marks: dict[str, float] = {"process_start": _T_PROCESS}
        self.compile_events: list[tuple[str, float, float]] = []
        self.state: dict = {}
        self.info: dict = {}                  # extra keys of the result line
        self.window: dict = {}
        self.trace = None                     # benchmarks.trace.Reduced
        self.device_kind = None

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = self.spans.get(name, 0.0) + (
                time.perf_counter() - t0)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--dry", action="store_true",
                   help="CPU rehearsal at the configuration's dry sizes")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="also read the control and the planted faults")
    p.add_argument("--registry", default=os.path.join(_ROOT, "BENCHMARK.json"),
                   help="the BENCHMARK.json to read (tests pass their own)")
    return p.parse_args(argv)


def _resolve(args):
    registry = load_json(args.registry)
    root = os.path.dirname(os.path.abspath(args.registry))
    cells = {w["name"]: w for w in registry["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in {args.registry}: "
                         f"{sorted(cells)}")
    cell = cells[args.workload]
    config = {c["name"]: c for c in registry["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, config["file"]))
    # Data files of the cell come from the registry's own tree; code
    # (windows, readers) from there first, then from this harness.
    bench_dirs = [os.path.join(root, p) for p in registry["paths"]]
    roots = bench_dirs + [_HERE]
    traffic = None
    for d in roots:
        path = os.path.join(d, "traffic", cell["traffic"] + ".json")
        if os.path.isfile(path):
            traffic = load_json(path)
            break
    if traffic is None:
        raise SystemExit(f"no traffic/{cell['traffic']}.json under {roots}")
    if args.dry:
        cfg = {**cfg, **cfg.get("dry", {})}
    return registry, cell, cfg, traffic, roots


def _device_or_exit(cell, dry):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not dry and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        sys.stderr.write(
            f"benchmarks/run.py: cell {cell['name']} needs {cell['chips']} "
            f"TPU chip(s); JAX found {len(devices)} x {dev.platform}. "
            "No result.\n")
        raise SystemExit(2)
    return devices[:cell["chips"]]


def _metrics_for(registry, cell_name, group, reported_e2e):
    """The metrics of ``group`` that this cell has to report."""
    out = []
    for m in registry[group]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported_e2e:
            out.append(m)
    return out


def main(argv=None) -> int:
    args = _parse(argv)
    registry, cell, cfg, traffic, roots = _resolve(args)
    if args.dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PHOTON_PALLAS_INTERPRET"] = "1"

    import jax

    devices = _device_or_exit(cell, args.dry)
    run = Run(args, cfg, traffic)
    run.device_kind = devices[0].device_kind
    jax.block_until_ready(jax.numpy.zeros((8,)) + 1)
    run.marks["first_device_op"] = time.perf_counter()

    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    run.info["compile_cache_dir"] = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: run.compile_events.append(
            (event, secs, time.perf_counter())))

    kind = load_module("windows", traffic["window"], roots)
    kind.setup(run)

    tracing = bool(args.trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    run.marks["window_start"] = time.perf_counter()
    setup_s = run.marks["window_start"] - _T_PROCESS
    win = kind.window(run, args.seconds)
    run.marks["window_end"] = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    run.window = win

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    run.state["memory_peak_bytes"] = peak
    kind.free(run)
    correct, numbers = kind.check(run, win)
    attempted, failed = kind.attempted_failed(win)
    correct = bool(correct and failed == 0)

    e2e = {"setup_s": setup_s, **kind.end_to_end(run, win)}
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics = {}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if tracing:
        from benchmarks import trace as trace_mod

        try:
            run.trace = trace_mod.reduce_dir(TRACE_DIR)
        except ValueError:
            if not args.dry:  # a CPU rehearsal has no device plane to read
                raise
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            line["breakdown"] = run.trace.breakdown()
        for m in _metrics_for(registry, cell["name"], "per_layer", set(e2e)):
            value = load_module("metrics", m["name"], roots).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in _metrics_for(registry, cell["name"], "end_to_end", set(e2e)):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line["end_to_end"] = e2e  # in a traced run too, for the record
    line.update(run.info)
    line["host_peak_rss_gb"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
    line["seed"] = run.seed
    line["spans_s"] = run.spans
    line["compared"] = numbers

    text = "compared (value <= limit): " + "  ".join(
        f"{k}={v['value']:.6g}<={v['limit']:g}" for k, v in numbers.items())
    sys.stderr.write(f"{text}\ncorrect={correct}\n")
    sys.stderr.flush()
    if args.dry:
        line = {"dry_run": True, "not_a_result": line}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
