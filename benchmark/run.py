"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it loads the cell's configuration, flow and
traffic by the names in ``BENCHMARK.json``, makes the data from the
seed, warms up, measures for ``--seconds`` through
``bytewax_tpu.run.cli_main`` in this process (the one that holds the
chip), checks what the sink received in the timed window against the
flow's plain reference, and prints one JSON object as the last line of
standard output.  Without a TPU (or with fewer chips than the cell
asks for) it exits non-zero and prints no result.
"""

import time

#: Taken before the heavy imports: ``setup_s`` counts them.
T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot give a result (no chip, no such cell, ...)."""


# -- the manifest and the cell's files ----------------------------------------


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, name: str, manifest: Optional[Dict[str, Any]] = None):
        self.manifest = manifest or load_json(ROOT, "BENCHMARK.json")
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.cfg = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.traffic = load_json(HERE, "traffic", f"{self.entry['traffic']}.json")
        self.flow = importlib.import_module(f"benchmark.flows.{self.cfg['flow']}")

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those
        that list it, and those that list no cell (a per-layer metric
        then goes with every cell that reports the metric it moves)."""

        def listed(m) -> bool:
            return "workloads" not in m or self.name in m["workloads"]

        end_to_end = [m for m in self.manifest["end_to_end"] if listed(m)]
        if group == "end_to_end":
            return end_to_end
        reported = {m["name"] for m in end_to_end}
        return [
            m
            for m in self.manifest["per_layer"]
            if listed(m) and m["moves"] in reported
        ]


# -- looking for the chip -----------------------------------------------------


def device_seen() -> Dict[str, Any]:
    """The device as jax reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_or_fail(chips: int) -> Dict[str, Any]:
    device = device_seen()
    if device["platform"] != "tpu":
        raise BenchFailure(f"jax found no TPU (platform {device['platform']!r})")
    if device["count"] < chips:
        raise BenchFailure(f"the cell asks for {chips} chips, jax has {device['count']}")
    return device


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# -- observing the run from outside -------------------------------------------


class Probe:
    """Reads the engine's own API plane (``GET /graph``, ``GET
    /status``) while a flow runs: once when the window starts and once
    when it ends, from the engine's main thread."""

    def __init__(self):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        os.environ["BYTEWAX_DATAFLOW_API_ENABLED"] = "1"
        os.environ["BYTEWAX_DATAFLOW_API_PORT"] = str(self.port)
        self.graphs: List[dict] = []
        self.status: Optional[dict] = None
        self.lowered: List[str] = []

    def _get(self, path: str) -> dict:
        url = f"http://127.0.0.1:{self.port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    def sample(self, _now: float = 0.0) -> None:
        self.graphs.append(self._get("/graph"))
        if self.status is None:
            self.status = self._get("/status")

    def watch(self, flow) -> None:
        """Note the steps the plan lowers to the device (the engine's
        own flatten pass marks them), to hold them to that tier."""
        from bytewax_tpu.engine.flatten import flatten

        self.lowered = [
            op.step_id
            for op in flatten(flow).ops
            if op.conf.get("_accel") is not None
        ]

    def off_device_steps(self) -> List[str]:
        """Lowered steps that a ``GET /graph`` reported on another
        tier than the device (all of them, where it answered fewer
        than twice or the plan lowered none)."""
        if len(self.graphs) < 2 or not self.lowered:
            return ["<no lowered step seen on GET /graph>"]
        off = []
        for graph in self.graphs:
            tiers = {n["step_id"]: n["tier"] for n in graph["steps"]}
            off += [s for s in self.lowered if tiers.get(s) != "device"]
        return off


def timed_sink(pack, packs: list, writes: list, on_write=None):
    """A sink that keeps each write as the arrays ``pack`` makes of it
    (no Python object of the results outlives the call, so the
    program's garbage collector finds nothing of the benchmark's to
    walk) and notes when it was written."""
    import jax

    from bytewax_tpu.outputs import DynamicSink, StatelessSinkPartition

    class _Part(StatelessSinkPartition):
        def write_batch(self, items) -> None:
            with jax.profiler.TraceAnnotation("bench_sink_write"):
                packs.append(pack(items))
                now = time.monotonic()
                writes.append((now, len(items)))
                if on_write is not None:
                    on_write(now)

    class _TimedSink(DynamicSink):
        def build(self, step_id, worker_index, worker_count):
            return _Part()

    return _TimedSink()


class Window:
    """The measured window: what the program's counters and the
    process's own accounts read at its start, and the profiler's
    stretch inside it."""

    def __init__(self, traffic, seconds: float, trace: bool, workdir: str):
        self.seconds = seconds
        self.t0: Optional[float] = None
        self.counters0: Dict[str, float] = {}
        self.phases0: Dict[str, float] = {}
        self.host0: Dict[str, float] = {}
        self.trace_dir = os.path.join(workdir, "trace") if trace else None
        self.trace_start_s = min(traffic.get("trace_start_s", 5), seconds * 0.25)
        self.trace_len_s = min(traffic.get("trace_seconds", 4), seconds * 0.5)
        self.traced: Optional[tuple] = None
        self._thread: Optional[threading.Thread] = None
        self._done = threading.Event()

    def start(self, now: float) -> None:
        from bytewax_tpu.engine import flight

        self.t0 = now
        self.setup_s = now - T_PROCESS
        self.counters0 = dict(flight.RECORDER.counters)
        self.phases0 = dict(flight.RECORDER.phase_totals)
        self.host0 = host_accounts()
        if self.trace_dir is not None:
            self._thread = threading.Thread(target=self._trace, daemon=True)
            self._thread.start()

    def _trace(self) -> None:
        import jax

        if self._done.wait(self.trace_start_s):
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        started = time.monotonic()
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._done.wait(self.trace_len_s)
        stopped = time.monotonic()
        jax.profiler.stop_trace()
        self.traced = (started - self.t0, stopped - self.t0)

    def close(self) -> Dict[str, Dict[str, float]]:
        """Stop the profiler if it still runs; what the counters, the
        phase ledger and the process's accounts gained over the
        window."""
        from bytewax_tpu.engine import flight

        self._done.set()
        if self._thread is not None:
            self._thread.join()

        def gained(after, before):
            return {k: v - before.get(k, 0) for k, v in after.items()}

        return {
            "counters": gained(dict(flight.RECORDER.counters), self.counters0),
            "phases": gained(dict(flight.RECORDER.phase_totals), self.phases0),
            "host": gained(host_accounts(), self.host0),
        }


def host_accounts() -> Dict[str, float]:
    """What the kernel and the interpreter have charged this process so
    far: CPU seconds and full garbage collections.  Printed with every
    run, to tell a noisy host from a changed program."""
    import gc
    import resource

    usage = resource.getrusage(resource.RUSAGE_SELF)
    return {
        "cpu_user_s": usage.ru_utime,
        "cpu_sys_s": usage.ru_stime,
        "gc_full_collections": gc.get_stats()[2]["collections"],
    }


# -- the two window drivers ---------------------------------------------------


def drive_stream(cell: Cell, data, window: Window, probe: Probe) -> Dict[str, Any]:
    """One ``cli_main`` over the scheduled stream: set-up rows, then
    the window until the deadline, then the drain at end of input."""
    import jax

    from benchmark.source import Schedule, scheduled_source
    from bytewax_tpu.run import cli_main

    def started(now: float) -> None:
        window.start(now)
        probe.sample()

    schedule = Schedule(
        warmup=cell.traffic["warmup"],
        poll_rows=int(cell.traffic["poll_rows"]),
        seconds=window.seconds,
        on_window_start=started,
        on_end=probe.sample,
    )
    packs: list = []
    writes: list = []
    source = scheduled_source(
        schedule, lambda lo, hi: cell.flow.batch(cell.cfg, data, lo, hi)
    )
    flow = cell.flow.build_flow(
        cell.cfg, data, source, timed_sink(cell.flow.pack, packs, writes)
    )
    probe.watch(flow)
    with jax.profiler.TraceAnnotation("bench_cli_main"):
        status = cli_main(flow)
    if status is not None or window.t0 is None:
        raise BenchFailure(f"the run did not reach end of input: {status!r}")
    gained = window.close()
    last_write = writes[-1][0] if writes else schedule.ended_at
    return {
        "events": schedule.window_rows,
        "window_s": max(last_write, schedule.ended_at) - window.t0,
        "schedule": schedule,
        "packs": packs,
        "results": sum(n for _at, n in writes),
        "basis": (data, schedule.served_rows),
        "poll_gap_s": schedule.max_gap_s,
        "poll_gap_in_window_s": max(
            b[0] - a[0] for a, b in zip(schedule.window_polls(), schedule.window_polls()[1:])
        ),
        **gained,
    }


def drive_jobs(cell: Cell, data, window: Window, probe: Probe) -> Dict[str, Any]:
    """Whole jobs back to back, each its own ``cli_main``: one in
    set-up, then a new one started while the window's seconds are not
    up, then one more that is not timed.  The API plane is read in the
    first and the last, so no job of the window waits for it
    (``demotion_count`` holds those to the device)."""
    import jax

    from bytewax_tpu.run import cli_main

    def job(on_write=None) -> Dict[str, Any]:
        packs: list = []
        writes: list = []
        flow = cell.flow.build_flow(
            cell.cfg, data, None, timed_sink(cell.flow.pack, packs, writes, on_write)
        )
        probe.watch(flow)
        with jax.profiler.TraceAnnotation("bench_cli_main"):
            status = cli_main(flow)
        if status is not None or not writes:
            raise BenchFailure(f"a job did not reach end of input: {status!r}")
        return {"packs": packs, "written": writes[-1][0], "results": writes[-1][1]}

    job(probe.sample)
    window.start(time.monotonic())
    jobs = []
    while time.monotonic() - window.t0 < window.seconds:
        jobs.append(job())
    gained = window.close()
    job(probe.sample)
    return {
        "events": data["rows"] * len(jobs),
        "window_s": jobs[-1]["written"] - window.t0,
        "jobs": jobs,
        "results": sum(j["results"] for j in jobs),
        "basis": (data,),
        **gained,
    }


DRIVERS = {"stream": drive_stream, "jobs": drive_jobs}


# -- deciding `correct` -------------------------------------------------------


def judge(cell: Cell, driven: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared, from what the sink received in the timed
    run against the flow's plain reference."""
    flow, cfg = cell.flow, cell.cfg
    want = flow.reference(cfg, *driven["basis"])
    if "jobs" in driven:
        per_job = [
            flow.compare(cfg, flow.result_arrays(cfg, j["packs"]), want)
            for j in driven["jobs"]
        ]
        return {k: max(c[k] for c in per_job) for k in per_job[0]}
    schedule = driven["schedule"]
    driven["open_comps"] = flow.undecided(
        cfg, driven["basis"][0], schedule.polls, schedule.ended_at
    )
    got = flow.result_arrays(cfg, driven["packs"])
    return flow.compare(cfg, got, want, driven["open_comps"])


def verdict(cell: Cell, numbers: Dict[str, float]) -> Dict[str, List[float]]:
    """Each number compared beside its limit, as ``[number, limit]``;
    a number the configuration gives no limit is an error."""
    limits = cell.cfg["limits"]
    return {name: [value, limits[name]] for name, value in numbers.items()}


def is_correct(checks: Dict[str, List[float]]) -> bool:
    return all(value <= limit for value, limit in checks.values())


# -- one run ------------------------------------------------------------------


def read_metrics(cell: Cell, group: str, run: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for m in cell.metrics(group):
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, device: Dict[str, Any]
) -> Dict[str, Any]:
    """Set-up, window, metrics and the comparison; returns the result
    line as a dict (with ``_run``, the internals, for the controls and
    the tests)."""
    workdir = tempfile.mkdtemp(prefix="benchmark-")
    cwd = os.getcwd()
    os.chdir(workdir)  # the API plane dumps dataflow.json into the cwd
    try:
        probe = Probe()
        t_backend = time.monotonic()
        data = cell.flow.make_data(cell.cfg, cell.traffic, seed, workdir)
        t_data = time.monotonic()
        window = Window(cell.traffic, seconds, trace, workdir)
        driven = DRIVERS[cell.traffic["mode"]](cell, data, window, probe)
        device = dict(device, memory_peak_bytes=memory_peak_bytes())
        t_check = time.monotonic()
        numbers = judge(cell, driven)
        numbers["off_device"] = (
            len(probe.off_device_steps())
            + int(driven["counters"].get("demotion_count", 0))
            + int(probe.status is None or probe.status["device"] != {
                k: device[k] for k in ("platform", "kind", "count")
            })
        )
        checks = verdict(cell, numbers)
        run = {
            "cell": cell,
            "data": data,
            "setup_s": window.setup_s,
            "device": device,
            "trace": None,
            **driven,
        }
        if trace and window.traced is not None:
            from benchmark import trace_reduce

            run["trace"] = trace_reduce.reduce_dir(window.trace_dir)
            run["trace"]["stretch_s"] = window.traced
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
        group = "per_layer" if trace else "end_to_end"
        line = {
            "correct": is_correct(checks),
            "attempted": int(driven["events"]),
            "failed": int(numbers["rows_unanswered"]),
            "metrics": read_metrics(cell, group, run),
            "device": device,
        }
        if run["trace"] is not None:
            line["breakdown"] = run["trace"]["breakdown"]
        line["info"] = {
            "workload": cell.name,
            "seed": seed,
            "events": int(driven["events"]),
            "window_s": driven["window_s"],
            "results": driven["results"],
            "jobs": len(driven.get("jobs", ())),
            "poll_gap_s": driven.get("poll_gap_s"),
            "poll_gap_in_window_s": driven.get("poll_gap_in_window_s"),
            "windows_undecided": len(driven.get("open_comps", ())),
            "compiles_in_window": driven["counters"].get("xla_compile_count", 0),
            "compile_s_in_window": driven["counters"].get("xla_compile_seconds", 0.0),
            "host": driven["host"],
            "traced_s": window.traced,
            "trace_bytes": run["trace"]["bytes"] if run["trace"] else None,
            "check_s": time.monotonic() - t_check,
            "setup_parts_s": {
                "imports_and_backend": t_backend - T_PROCESS,
                "data": t_data - t_backend,
                "warm_up": window.setup_s - (t_data - T_PROCESS),
            },
        }
        line["checks"] = checks
        line["_run"] = run
        return line
    finally:
        os.chdir(cwd)
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import bytewax_tpu  # noqa: F401

        cell = Cell(args.workload)
        device = device_or_fail(cell.chips)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    except (BenchFailure, ImportError) as ex:
        print(f"benchmark.run: {ex}", file=sys.stderr)
        return 1
    line.pop("_run")
    print(json.dumps(line["info"]), file=sys.stderr)
    for name, (value, limit) in line["checks"].items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
