#!/usr/bin/env python3
"""tpulab's benchmark: one cell, one run, one JSON line.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

One process a run.  This process holds the chip: it builds the model from
the seed, starts the program's own gRPC server in-process, holds the served
path to the plain reference, warms up, and then drives the client process
(``perf/loadgen/client.py``, which never imports JAX) through a window of
exactly ``--seconds``.  Progress goes to earlier lines; the last line of
stdout is the result (``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``).

``--trace 0``  the cell's end-to-end metrics, the profiler off.
``--trace 1``  profiles a slice inside the window (Python tracer on) and
               reports the per-layer metrics only: a run of its own, whose
               window is no measurement.
``--trace 2``  ``--trace 0`` to the letter until the window has closed and
               its numbers are taken; then, in the same process and on the
               same traffic (the client keeps it up), starts and stops the
               profiler once for nothing, traces ``TRACE_SECONDS`` with the
               program's own switch (``tpulab.utils.tracing``), and reports
               both kinds of metric on one line: end-to-end numbers and
               counter deltas from the window, trace and sampler readings
               from the traced tail.

A cell needs a TPU with at least its ``chips``: anything else exits non-zero
before it measures.  There is no CPU fallback.  (``--allow-cpu`` exists for
``perf/tests`` alone, which run throw-away cells that are not in
``BENCHMARK.json``; with it the result line is labelled ``rehearsal``.)
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
for _p in (PERF_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
sys.dont_write_bytecode = True

from harness import spec  # noqa: E402
from harness.child import ClientProcess  # noqa: E402
from harness.window import reduce_window  # noqa: E402

#: everything a run leaves behind lives here (git-ignored): the compilation
#: cache at a fixed path (the path is part of the cache's key), the native
#: host core, the last trace
CACHE_DIR = os.path.join(PERF_DIR, ".cache")
EXIT_NO_CHIP = 2
#: a run that compiles may take 1200 s; past this every thread's stack goes
#: to stderr and the process exits 1 without a result.  After the result line
#: the interpreter gets EXIT_GRACE_S to end its threads.
DEADLINE_S, EXIT_GRACE_S = 1150, 60
#: the traced slice of the window: starts this far in, lasts this long
TRACE_START_S, TRACE_SECONDS = 2.0, 3.0
#: --trace 2: how long the client keeps the traffic up after the window if
#: this process never says stop (it says so as soon as the trace is taken)
TRACE_TAIL_MAX_S = 60.0
GAUGE_PERIOD_S = 0.05


def say(msg: str) -> None:
    print(f"[perf {time.monotonic() - T_PROCESS_START:7.1f}s] {msg}",
          flush=True)


def build_native_core() -> str:
    """The C++ host core, built once from ``cpp/`` into the cache directory
    (a checkout holds only what git commits, so there is no ``cpp/build``).
    The children are compilers and never touch JAX."""
    out = os.path.join(CACHE_DIR, "native")
    lib = os.path.join(out, "libtpulab_native.so")
    if not os.path.exists(lib):
        for cmd in (["cmake", "-S", os.path.join(ROOT, "cpp"), "-B", out,
                     "-G", "Ninja"], ["ninja", "-C", out, "tpulab_native"]):
            subprocess.run(cmd, check=True, stdout=subprocess.DEVNULL,
                           timeout=300)
    return lib


class CompileCounter:
    """Counts JAX's backend-compile events (a persistent-cache load is one
    too) between ``start`` and ``stop``: inside the window there should be
    none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.total = 0
        self.in_window = 0
        self._armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.total += 1
            if self._armed:
                self.in_window += 1

    def start(self) -> None:
        self._armed = True

    def stop(self) -> None:
        self._armed = False


class GaugeSampler(threading.Thread):
    """Reads the adapter's cheap gauges every 50 ms (traced runs only)."""

    def __init__(self, adapter):
        super().__init__(name="perf-gauge", daemon=True)
        self.adapter, self.samples, self._stop_evt = adapter, [], threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(GAUGE_PERIOD_S):
            self.samples.append(self.adapter.gauge())

    def stop(self) -> None:
        self._stop_evt.set()
        self.join(timeout=5)


def trace_slice(trace_dir: str, t_start: float, span: list) -> None:
    """Profile ``TRACE_SECONDS`` of the window, starting ``TRACE_START_S``
    after ``t_start``, inside one host span the reducer clips to; ``span``
    gets the span's own start and end on this process's monotonic clock."""
    time.sleep(max(0.0, t_start + TRACE_START_S - time.monotonic()))
    hold_window_span(trace_dir, span, python_tracer=True)


def hold_window_span(trace_dir: str, span: list, **start_kw) -> None:
    """One capture through the program's switch, ``TRACE_SECONDS`` long,
    all of it inside the host span the reducer clips to."""
    from harness.trace_reduce import WINDOW_SPAN
    from tpulab.utils import tracing
    tracing.start(trace_dir, **start_kw)
    try:
        with tracing.annotate(WINDOW_SPAN):
            t0 = time.monotonic()
            time.sleep(TRACE_SECONDS)
            span[:] = [t0, time.monotonic()]
    finally:
        tracing.stop()


def trace_tail(trace_dir: str, adapter, span: list) -> list:
    """``--trace 2``, after the window has closed: start and stop the
    profiler once and throw that away (the first start's cost falls into
    no number), then trace ``TRACE_SECONDS`` of the traffic the client
    keeps up, sampling the gauges meanwhile; returns the samples."""
    from tpulab.utils import tracing
    tracing.start(trace_dir + ".first")
    tracing.stop()
    shutil.rmtree(trace_dir + ".first", ignore_errors=True)
    sampler = GaugeSampler(adapter)
    sampler.start()
    try:
        hold_window_span(trace_dir, span)
    finally:
        sampler.stop()
    return sampler.samples


def device_block(devices, chips: int) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:chips]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def read_metrics(cell, kind: str, entries, ctx) -> dict:
    """Each metric is a reader of its own (``<kind>/<name>.py``); one that
    finds nothing to read returns None and is left out."""
    out = {}
    for m in entries:
        value = cell.module(kind, m["name"]).read(ctx)
        if value is None:
            say(f"metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0,
                    help="0: end-to-end metrics, profiler off; 1: per-layer "
                    "metrics from a slice profiled inside the window; 2: as "
                    "0, then a traced tail of the same traffic in the same "
                    "run, and both kinds of metric")
    ap.add_argument("--benchmark", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--allow-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    cell = spec.load_cell(args.workload, args.benchmark)
    rehearsal = bool(args.allow_cpu and args.benchmark)

    os.makedirs(CACHE_DIR, exist_ok=True)
    if not rehearsal:
        os.environ["TPULAB_NATIVE_LIB"] = build_native_core()
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CACHE_DIR, "jax")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) < cell.chips):
        print(f"perf/run.py: cell {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return EXIT_NO_CHIP
    if not rehearsal:
        # a device the table of peaks does not know is an error, not a default
        peaks = spec.load_json(os.path.join(PERF_DIR, "peaks.json"))
        if devices[0].device_kind not in peaks["devices"]:
            print(f"perf/run.py: device_kind {devices[0].device_kind!r} is "
                  "not in perf/peaks.json", file=sys.stderr)
            return EXIT_NO_CHIP
    from tpulab import native
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, {args.seconds:g} s, "
        f"trace {args.trace}; {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}, native_core="
        f"{str(native.enabled()).lower()}, compile cache {cache}")

    compiles = CompileCounter()
    generator = cell.module("loadgen", cell.traffic["generator"])
    plan = generator.plan(cell.traffic, args.seed, args.seconds)
    adapter = cell.module("models", cell.config["kind"]).build(
        cell, args.seed, say)
    client = ClientProcess()
    sampler = None
    try:
        adapter.build()
        say(f"built and serving on port {adapter.port} "
            f"({compiles.total} compilations so far)")
        client.call({"op": "connect", "port": adapter.port,
                     "channels": plan["channels"]})
        # the client builds the window's payloads while this process checks
        # and warms up
        reference_ok = adapter.check_reference(client)
        n0 = compiles.total
        adapter.warm_up(client)
        say(f"warm-up done ({compiles.total - n0} compilations in it)")
        client.send({"op": "window", "plan": plan, "seed": args.seed,
                     "tail_s": TRACE_TAIL_MAX_S if args.trace == 2 else 0,
                     **adapter.window_args()})
        client.expect("ready")

        t_go = time.monotonic()
        client.send({"op": "go"})
        opened = client.expect("opened")    # at once, or after the ramp
        say(f"window opened {opened['t_start'] - t_go:.1f} s after go")
        before = adapter.counters()
        host_span: list = []
        trace_dir = os.path.join(CACHE_DIR, "trace", cell.name)
        compiles.start()
        if args.trace == 1:
            sampler = GaugeSampler(adapter)
            sampler.start()
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = threading.Thread(
                target=trace_slice,
                args=(trace_dir, opened["t_start"], host_span), daemon=True)
            tracer.start()
        closed = client.expect("closed")
        setup_s = closed["t_start"] - T_PROCESS_START
        compiles.stop()
        after = adapter.counters()
        gauges = []
        if sampler is not None:
            sampler.stop()
            gauges = sampler.samples
        say(f"window closed after {closed['t_end'] - closed['t_start']:.3f} s"
            f"; draining")
        if args.trace == 2:
            # the window's numbers are taken: everything from here on is
            # the traced tail, on the traffic the client keeps up
            in_window = compiles.in_window
            compiles.start()
            shutil.rmtree(trace_dir, ignore_errors=True)
            try:
                gauges = trace_tail(trace_dir, adapter, host_span)
            finally:
                client.send({"op": "stop"})
            compiles.stop()
            say(f"traced tail: {host_span[1] - host_span[0]:.3f} s, "
                f"{host_span[0] - closed['t_end']:.3f} s after the window; "
                f"compilations: {in_window} in the window, "
                f"{compiles.in_window - in_window} in the tail")
        result = client.expect("done")["result"]
        if args.trace == 1:
            tracer.join(timeout=120)
        device = device_block(devices, cell.chips)
    finally:
        client.close()
        adapter.shutdown()

    win = reduce_window(result)
    # an open loop's arrivals after the window: no requests of the window,
    # but their tokens are the traced tail's
    win["records"] = win["records"] + result.get("tail_requests", [])
    say(f"attempted {win['attempted']}, failed {win['failed']} "
        f"(completed but wrong: {win['invalid']}), completed "
        f"{len(win['completed'])}" + (f"; errors: {win['errors']}"
                                      if win["errors"] else ""))
    if "dispatch" in after:
        moved = {k: v - before["dispatch"][k]
                 for k, v in after["dispatch"].items()
                 if isinstance(v, int) and not isinstance(v, bool)
                 and v != before["dispatch"][k]}
        kinds = {k: v - before["dispatch"]["kinds"][k]
                 for k, v in after["dispatch"]["kinds"].items()}
        say(f"scheduler counters over the window: {moved} kinds={kinds}")
        if "stages" in after["dispatch"]:
            split = {k: v["s"] - before["dispatch"]["stages"][k]["s"]
                     for k, v in after["dispatch"]["stages"].items()}
            say("scheduler stages over the window, seconds: " + ", ".join(
                f"{k} {v:.3f}" for k, v in split.items())
                + f"; sum {sum(split.values()):.3f} of {win['seconds']:.3f}")
    if compiles.in_window:
        say(f"WARNING: {compiles.in_window} compilation(s) inside the "
            "measured window: a shape was not warmed up")
    ctx = {"window": win, "cell": cell, "plan": plan, "setup_s": setup_s,
           "counters_before": before, "counters_after": after,
           "gauges": gauges,
           "compiles_in_window": compiles.in_window, "trace": None, "say": say}
    out = {"correct": bool(reference_ok and win["invalid"] == 0
                           and win["attempted"] > 0),
           "attempted": win["attempted"], "failed": win["failed"]}
    if args.trace:
        from harness.trace_reduce import find_xplane, reduce_trace
        try:
            trace = reduce_trace(find_xplane(trace_dir), cell.chips)
        except ValueError:
            if not rehearsal:       # a CPU trace has no TPU plane
                raise
            trace = None
        if args.trace == 2:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if args.trace and trace is not None:
        ctx["trace"] = trace
        trace["host_span"] = host_span
        lo, hi = host_span
        in_span = sum(1 for r in win["records"] if not r.get("error")
                      for t in r.get("times", ()) if lo <= t <= hi)
        say(f"traced slice: {in_span} tokens reached the client in "
            f"{hi - lo:.3f} s ({in_span / (hi - lo):.1f} a second, the "
            "profiler on)")
        say(f"trace: window {trace['window_s']:.3f} s, device busy "
            f"{trace['busy_s']:.3f} s; programs: " + ", ".join(
                f"{k} x{v['count']} {v['total_s']:.3f}s"
                for k, v in sorted(trace["modules"].items(),
                                   key=lambda kv: -kv[1]["total_s"])[:8]))
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["metrics"] = {}
    if args.trace != 1:
        out["metrics"].update(read_metrics(cell, "e2e_metrics",
                                           cell.end_to_end, ctx))
    if args.trace:
        out["metrics"].update(read_metrics(cell, "layer_metrics",
                                           cell.per_layer, ctx))
    out["device"] = device
    if rehearsal:
        out["rehearsal"] = "CPU run of a test cell: not a measurement"
    print(json.dumps(out), flush=True)
    faulthandler.dump_traceback_later(EXIT_GRACE_S, exit=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
