"""One run of one cell: find its files by name, check the device, set
up, measure, check the answers, report.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
(``configs`` entry, whose ``file`` holds it) and a traffic mix, found as
``traffic/<mix>.json`` under the benchmark's directory; the mix's
``kind`` names its driver, ``drivers/<kind>.py``; each per-layer metric
is read by ``metrics/<name>.py``.  Adding a cell, a mix, a configuration
or a metric therefore adds files and entries and edits none.

A driver module provides

    setup(run) -> state          build and warm the system under test
    measure(state, window)       drive it for ``run.seconds``; calls
                                 ``window.begin()``/``window.end()``
    report(state, window) -> (end-to-end metrics, attempted, failed)
    verify(state, control) -> [(name, value, limit)], each value
                                 compared with its limit after the window
    close(state)

and may provide

    prepared(run) -> bool        whether state that outlives a run (a
                                 query server's store) is on disk
    prepare(run)                 make it; the entry runs this in a child
                                 process before the set-up clock starts

and a metric module ``read(window) -> number or None``.
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import importlib.util
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoDevice(RuntimeError):
    """The chips the cell asks for are not there."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tree_digest(root: Path, pattern: str = "**/*.py") -> str:
    h = hashlib.sha256()
    for p in sorted(Path(root).glob(pattern)):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:12]


class Run:
    """What a driver needs: the cell, its configuration and mix, the
    seed and window length, and a scratch directory inside the
    checkout."""

    def __init__(self, root: Path, workload: str, seed: int, seconds: int,
                 trace: bool, control: bool):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.home = self.root / self.bench["paths"][0]
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = json.loads(
            (self.root / configs[self.cell["config"]]["file"]).read_text())
        self.config["name"] = self.cell["config"]
        self.traffic = json.loads((self.home / "traffic" /
                                   f"{self.cell['traffic']}.json").read_text())
        self.driver = load_module(
            self.home / "drivers" / f"{self.traffic['kind']}.py",
            f"chipbench_driver_{self.traffic['kind']}")
        self.seed = int(seed)
        self.seconds = int(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.work = self.home / "_work"
        self.work.mkdir(exist_ok=True)

    def note(self, line: str) -> None:
        """A line for the log (stderr), before the result."""
        print(line, file=sys.stderr, flush=True)

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]

    def per_layer(self) -> list:
        names = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in names
                and self.cell["name"] in m.get("workloads",
                                               [self.cell["name"]])]


class Window:
    """The measured window: telemetry spans and counters over it, JAX
    compilations inside it, and with ``--trace 1`` the device trace."""

    def __init__(self, run: Run):
        self.run = run
        self.compiles = 0
        self._open = False
        self.t0 = self.t1 = None          # perf_counter seconds
        self.spans, self.dropped, self.epoch = [], 0, 0.0
        self.counters = {}
        self.device = None                # tracing.Reduced
        self.loadgen = None               # query cells: rows of requests
        self.shapes = {}                  # set by the driver
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self._open and event in COMPILE_EVENTS:
            self.compiles += 1

    def _counters(self) -> dict:
        from repro.core.telemetry import metrics
        out = {}
        for kind, name, m in metrics.REGISTRY.collect():
            if kind == "counter":
                out[name] = out.get(name, 0) + m.value
        return out

    def begin(self) -> None:
        from repro.core.telemetry import trace
        if self.run.trace:
            import jax
            self._trace_dir = self.run.work / "trace"
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self._trace_dir))
        trace.reset()
        self.epoch = trace.TRACER._epoch
        self._c0 = self._counters()
        self._cpu0 = time.process_time()
        self._open = True

    def end(self, t0: float, t1: float) -> None:
        """Close the window; [t0, t1] (perf_counter) is what it
        measured."""
        from repro.core.telemetry import trace
        self._open = False
        self.t0, self.t1 = t0, t1
        self.run.note(f"host: process cpu {time.process_time() - self._cpu0}"
                      f" s over {t1 - t0} s")
        self.spans = trace.TRACER.spans()
        self.dropped = trace.TRACER.dropped
        c1 = self._counters()
        self.counters = {k: v - self._c0.get(k, 0) for k, v in c1.items()}
        if self.run.trace:
            import jax
            from chipbench import tracing
            t_mark = tracing.mark()
            jax.profiler.stop_trace()
            record = tracing.load(str(self._trace_dir), t_mark)
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self.device = tracing.Reduced(record, t0, t1)
            tracing.save({"record": record, "t0": t0, "t1": t1,
                          "spans": self.spans, "epoch": self.epoch},
                         str(self.run.work / "last_trace.json.gz"))

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s["name"] == name
                and self.t0 <= self.epoch + s["ts"] / 1e6 <= self.t1]


def check_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform "
                       f"{devices[0].platform!r}); this benchmark measures "
                       f"the chip and never falls back to the CPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_memory() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the control (the reference with one "
                         "guarantee broken) in the program's place")
    ap.add_argument("--prepare", type=int, choices=(0, 1), default=0,
                    help="only make the state the cell keeps between runs "
                         "(its driver's prepare) and print no result")
    return ap.parse_args(argv)


def run_cell(argv, *, root: Path, t_start: float, device: dict) -> dict:
    """One run on ``device`` (as ``check_device`` reports it); returns
    the result line as a dict."""
    args = parse(argv)
    run = Run(root, args.workload, args.seed, args.seconds, args.trace,
              args.control)
    run.device_kind = device["kind"]
    sys.path.insert(0, str(run.root / "src"))
    drv = run.driver
    window = Window(run)
    state = drv.setup(run)
    try:
        gc.collect()
        setup_s = time.monotonic() - t_start
        drv.measure(state, window)
        device["memory_peak_bytes"] = peak_memory()
        metrics, attempted, failed = drv.report(state, window)
        checks = drv.verify(state, run.control)
    finally:
        drv.close(state)
    run.note(f"compiles_in_window {window.compiles}")
    if window.dropped:
        run.note(f"telemetry spans dropped {window.dropped}")
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for m in
             run.bench["end_to_end"] + run.bench["per_layer"]}
    if run.trace:
        out = {}
        for m in run.per_layer():
            reader = load_module(run.home / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" + m["name"].replace(
                                     ".", "_"))
            v = reader.read(window) if not window.dropped else None
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
        dev = window.device
        device["busy_s"] = dev.busy_s
        device["window_s"] = dev.window_s
        result["metrics"] = out
        result["device"] = device
        result["breakdown"] = {
            "device_ops": dev.top_ops(),
            "idle_gaps": dev.idle_gaps(window.spans, window.epoch)}
    else:
        metrics["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": float(metrics[m["name"]]),
                                         "unit": m["unit"]}
                             for m in run.end_to_end()}
        result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n} {v} limit {lim}", file=sys.stderr)
    return result


def main(argv, *, root: Path, t_start: float) -> int:
    """The benchmark's entry: on the chip only, with JAX's persistent
    compilation cache inside the checkout.

    Where the driver keeps state between runs and it is not on disk yet,
    a child process makes it first, before this process touches the
    chip; its time is left out of ``setup_s``, so that ``setup_s`` always
    measures the same restart."""
    args = parse(argv)
    run = Run(root, args.workload, args.seed, args.seconds, args.trace,
              args.control)
    prepared = getattr(run.driver, "prepared", None)
    if not args.prepare and prepared and not prepared(run):
        t = time.monotonic()
        rc = subprocess.call([sys.executable, str(run.home / "run.py"),
                              *argv, "--prepare", "1"], cwd=root)
        if rc:
            return rc
        prepare_s = time.monotonic() - t
        run.note(f"prepared in a child process in {prepare_s} s, "
                 f"outside setup_s")
        t_start += prepare_s
    try:
        device = check_device(int(run.cell["chips"]))
    except NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    import jax
    sys.path.insert(0, str(root / "src"))
    from repro import compile_cache
    compile_cache.enable()
    # cache every program, however quick its compile, so that later runs
    # of a cell compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.prepare:
        run.driver.prepare(run)
        return 0
    result = run_cell(argv, root=root, t_start=t_start, device=device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
