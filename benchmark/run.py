"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process loads, warms, measures for ``--seconds`` and prints one JSON
object as the last line of its standard output (the contract's keys). It
fails, printing no result, without a TPU or with fewer chips than the cell
asks for. ``--rehearse-cpu`` is the explicit toy rehearsal of the control flow
on the CPU: it prints no metric at all.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_start_wall() -> float:
    """When this process was created, on ``time.time()``'s clock."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))


class Cell:
    """What a driver gets: the cell's data, the clock marks and the tracer."""

    def __init__(self, args, workload, config, mix):
        self.workload, self.seed, self.seconds = workload["name"], args.seed, args.seconds
        self.trace, self.rehearsal = bool(args.trace), args.rehearse_cpu
        self.chips, self.config, self.mix = workload["chips"], config, mix
        self.out_dir = os.path.join(ROOT, ".bench_out", self.workload)
        self.marks = {"process": process_start_wall()}
        self.tracing = False
        self.trace_dir = os.path.join(self.out_dir, "trace")
        self.window = [None, None]
        self.trace_window = [None, None]
        self.peak_bytes = None
        self.lowerings = 0
        self._in_window = False

    def mark(self, name):
        self.marks.setdefault(name, time.time())

    def lap(self, what):
        """A line on standard error: seconds since the last lap (set-up study)."""
        now = time.time()
        self.note(f"lap {what}: {now - getattr(self, '_lap', self.marks['process']):.2f} s")
        self._lap = now

    def note(self, text):
        print(f"[bench] {text}", file=sys.stderr, flush=True)

    def on_lowering(self, event, *_a, **_k):
        if self._in_window and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def start_window(self):
        self.mark("window")
        self.window[0] = time.time()
        self._in_window = True

    def end_window(self):
        import jax

        self._in_window = False
        self.window[1] = time.time()
        if self.tracing:
            self.trace_stop()
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)

    def trace_start(self):
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.tracing = True
        self.trace_window[0] = time.time()

    def trace_stop(self):
        import jax

        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_window[1] = time.time()


def reader(name: str):
    """A per-layer metric's reader: ``benchmark/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse_cpu: bool):
    """The cell's entries of ``BENCHMARK.json``, its configuration and its mix
    (at the toy sizes of ``checks/tiny.json`` for the CPU rehearsal), with the
    environment set as a run needs it. Before jax is imported: the compile
    cache at a fixed path inside the checkout (unless placed from outside),
    and everything the program writes inside the checkout too."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next((w for w in bench["workloads"] if w["name"] == name), None)
    if workload is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == workload["config"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["MAGGY_TPU_LOG_ROOT"] = os.path.join(ROOT, ".bench_out", "logs")
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("MAGGY_TPU_COMPILE_CACHE", "0")
    else:
        cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
        os.makedirs(cache_dir, exist_ok=True)
    sys.path.insert(0, ROOT)

    from benchmark import configs, traffic

    config = configs.load(entry["file"])
    mix = traffic.load_mix(workload["traffic"])
    if rehearse_cpu:
        with open(os.path.join(HERE, "checks", "tiny.json")) as f:
            tiny = json.load(f)
        config = merge(config, tiny["config"])
        mix = merge(mix, tiny["traffic"][mix["kind"]])
    return bench, workload, config, mix


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="toy sizes on the CPU; prints no metric")
    args = parser.parse_args(argv)

    bench, workload, config, mix = load_cell(args.workload, args.rehearse_cpu)
    cell = Cell(args, workload, config, mix)
    os.makedirs(cell.out_dir, exist_ok=True)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.monitoring.register_event_duration_secs_listener(cell.on_lowering)
    cell.lap("interpreter and imports")
    devices = jax.devices()
    cell.mark("devices")
    cell.lap("backend")
    platform = devices[0].platform
    if not args.rehearse_cpu and platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found {platform} devices only")
    if len(devices) < workload["chips"]:
        raise SystemExit(f"the cell asks for {workload['chips']} chips, JAX found {len(devices)}")

    driver = importlib.import_module(f"benchmark.kinds.{mix['kind']}")
    result = driver.run(cell)

    m = cell.marks
    phases = {
        "start_s": m["devices"] - m["process"],
        "build_s": m["built"] - m["devices"],
        "compile_s": m["compiled"] - m["built"],
        "warm_s": m["window"] - m["compiled"],
    }
    cell.note("set-up " + json.dumps({k: round(v, 3) for k, v in phases.items()}))
    obs = result["obs"]
    obs.update(phases=phases, lowerings_in_window=cell.lowerings, peak_bytes=cell.peak_bytes,
               device_kind=devices[0].device_kind, cell=cell)
    device = {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices),
        "memory_peak_bytes": cell.peak_bytes,
    }
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}
    values = dict(result["end_to_end"], setup_s=m["window"] - m["process"])
    if args.trace and not args.rehearse_cpu:
        from benchmark import trace as trace_mod

        summary = trace_mod.reduce(cell.trace_dir, chips=workload["chips"])
        obs["trace"] = summary
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"][:10], "idle_gaps": summary["idle_gaps"][:10]}
        for metric in bench["per_layer"]:
            if applies(metric, cell.workload):
                value = reader(metric["name"]).read(obs)
                if value is not None:
                    line["metrics"][metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    else:
        for metric in bench["end_to_end"]:
            if applies(metric, cell.workload):
                line["metrics"][metric["name"]] = {"value": float(values[metric["name"]]), "unit": metric["unit"]}
    if args.rehearse_cpu:
        line["metrics"], line["rehearsal"] = {}, True
        for k in ("busy_s", "window_s", "memory_peak_bytes"):
            device.pop(k, None)
        line.pop("breakdown", None)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
