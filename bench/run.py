#!/usr/bin/env python3
"""The on-chip serving benchmark of the OmniSense detector pod.

    python bench/run.py --workload det2-overload --seed 7 --seconds 30 --trace 0

One run: build the cell's pod (``bench/pod.py``) from its configuration
file, warm every shape the cell's traffic can use, then drive the
program's open loop on the host clock for ``--seconds`` (``window.py``;
the round in flight at the close finishes inside the window), check
what the window served against the plain reference
(``check.py``), and print one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` (each compared number
beside its limit, also the last lines on standard error).

Everything a cell is made of is found by name from files:
``BENCHMARK.json`` names the cell's configuration and traffic mix,
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``
hold them, and each metric is read by ``bench/metrics/<name>.py``
(a suffix after the first dot, as in ``batch_fill.overload``, names the
same reader).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.

It runs in one process and starts none that needs the chip.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero
before doing any work.  JAX's persistent compilation cache is kept in
``.jax_cache`` at the root of the checkout, whatever the environment
says, and keeps every program, however fast it compiled.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check as check_mod  # noqa: E402
from bench.flops import forward_flops  # noqa: E402
from bench.schedule import schedule  # noqa: E402


class NoChip(Exception):
    pass


# --------------------------------------------------------------------------
# what a cell is made of, by name
# --------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark: dict | None = None,
              bench_dir: Path = BENCH) -> dict:
    """The cell ``name``: its workload entry, configuration, traffic mix
    and the metrics it reports (``end_to_end`` and ``per_layer``)."""
    bm = benchmark or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "workload": w,
        "config": load_json(bench_dir / "configs" / f"{w['config']}.json"),
        "mix": load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
        "per_layer": [m for m in bm["per_layer"] if mine(m)],
    }


def reader(metric: str, bench_dir: Path = BENCH):
    """``read(run)`` of ``bench/metrics/<metric>.py``, or of the file of
    the name before its first dot."""
    for stem in (metric, metric.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{metric}.py")


def peak(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(peaks)}")
    return peaks[kind]


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

class CompileClock:
    """Seconds of XLA compilation and persistent-cache hits, from JAX's
    monitoring events (a copy of ``chip_smoke.CompileClock``), plus how
    many compiles ran."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class CompileLog(logging.Handler):
    """Collects JAX's "Compiling ..." log lines while switched on."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines: list[str] = []

    def emit(self, record) -> None:
        msg = record.getMessage()
        if "ompil" in msg:
            self.lines.append(msg.split("\n")[0][:300])


def configure_cache() -> str:
    import jax

    from repro.launch.serve import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cache is the checkout's own: never evict (a machine may set a
    # size limit for a shared cache in its environment)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def warm(pod, config: dict, mark=lambda name: None) -> None:
    """Run every program the cell's traffic can call, at every shape:

    * warm-up traffic: ``warmup_rounds`` rounds in which every stream
      hands the pod its next frame (explore frames included), through
      ``serve_open_batch`` as the window does;
    * each rung's batched dispatch at every chunk size up to the top
      bucket (stack, pad, forward, decode and per-row slices), with the
      projection at both of its padded sizes (one and the top bucket);
    * the device NMS at every (rows, padded detections) it can take;
    * each rung's full-ERP discovery forward.

    The warm-up traffic has no seed: it is the same in every run.
    ``mark(name)`` is called as each of the three parts ends."""
    import numpy as np

    from repro.core.sphere import nms_auto_backend, sph_nms_batch
    from repro.core.sroi import SRoI
    from repro.serving.traffic import Arrival

    server, backend = pod.server, pod.backend
    n = config["streams"]
    for r in range(config["warmup_rounds"]):
        server.serve_open_batch([Arrival(t_s=float(r), stream=s, frame_idx=r)
                                 for s in range(n)])
    mark("warm rounds")
    # one frame for every crop; the crop cache makes the later calls
    # hits, so only the b = 1 and b = max calls upload frames
    frame = pod.frames.pool[0][0]
    top = pod.buckets.max_batch
    for v in pod.loops[0].variants:
        regions = [SRoI(center=(0.4 * k - 1.5, 0.1 * k - 0.3), fov=(0.9, 0.7))
                   for k in range(top)]
        backend.launch_srois_batched([(frame, regions[-1])], v)()
        for b in [top] + list(range(1, top + 1)):
            backend.launch_srois_batched([(frame, r) for r in regions[:b]],
                                         v)()
        backend.infer_erp(frame, v)
    mark("warm shapes")
    rng = np.random.default_rng(0)
    sizes = list(config["nms_sizes"])
    sizes += [sizes[-1] * k for k in range(2, config["nms_top_multiples"] + 1)]
    for size in sizes:
        for rows in range(1, n + 1):
            if nms_auto_backend(rows, size) != "device":
                continue
            boxes = np.stack([rng.uniform(-3, 3, (rows, size)),
                              rng.uniform(-1.2, 1.2, (rows, size)),
                              rng.uniform(0.05, 0.9, (rows, size)),
                              rng.uniform(0.05, 0.9, (rows, size))], -1)
            sph_nms_batch(boxes, rng.uniform(size=(rows, size)),
                          np.ones((rows, size), bool),
                          iou_threshold=config["nms_threshold"])
    mark("warm nms")


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

class Run:
    """What one window recorded; the metric readers read this."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Session:
    """A cell's pod, built and warmed: :meth:`window` serves one measured
    window on it and checks it.  A benchmark run serves one window on a
    fresh session.  ``config_overrides`` (a smaller configuration) exists
    for the CPU tests, which also serve several windows on one session
    and plant faults in ``pod`` between them."""

    def __init__(self, cell: dict, seed: int, trace: bool, *, device=None,
                 cache: bool = True, config_overrides=None, log=print):
        import jax

        from bench import pod as pod_mod
        from bench import window

        self.cell, self.trace, self.log = cell, trace, log
        self.config = config = dict(cell["config"],
                                    **(config_overrides or {}))
        self.device = device or jax.devices()[0]
        self.cache_dir = configure_cache() if cache else None
        self.clock = CompileClock()
        phases = [("start", time.perf_counter())]
        self.params = pod_mod.init_weights(config)
        phases.append(("weights", time.perf_counter()))
        pool = pod_mod.render_pool(config, pod_mod.make_videos(config))
        phases.append(("frames", time.perf_counter()))
        self.sink = window.make_sink()
        self.pod = pod_mod.build_pod(config, self.params,
                                     pod_mod.PooledFrames(pool),
                                     telemetry=self.sink)
        self.capture = window.Capture(seed,
                                      config["check_dispatches_per_rung"])
        self.capture.attach(self.pod, config["detectors"][0]["n_classes"])
        self.spans = window.Spans()
        if trace:
            window.attach_spans(self.pod, self.spans)
        self.pod.server.open_loop_begin(config["slo_s"])
        phases.append(("pod", time.perf_counter()))
        warm(self.pod, config, lambda name: phases.append(
            (name, time.perf_counter())))
        self.pod.server.open_loop_end()
        self.setup_phases = [(name, t1 - t0) for (_, t0), (name, t1)
                             in zip(phases, phases[1:])]
        self.frame_base = config["warmup_rounds"]
        self.t_base = float(self.frame_base + 1)

    def window(self, seed: int, seconds: float, *, free: bool = False
               ) -> dict:
        """Serve and check one window; returns the result line as a dict.
        ``free`` drops the pod before the reference runs (one run per
        process)."""
        import jax

        from bench import reference as ref
        from bench import tracefile, window

        cell, config, log, clock = self.cell, self.config, self.log, \
            self.clock
        pod, sink, capture, spans = self.pod, self.sink, self.capture, \
            self.spans
        due = schedule(cell["mix"], seconds, seed)
        if not due:
            raise ValueError("the traffic mix offers no frame in the window")
        frame_base = self.frame_base
        # each window is one open-loop run of the program, its event
        # times after everything the clock has booked
        pod.server.open_loop_begin(config["slo_s"])
        self.t_base = max(self.t_base, pod.server.clock.now + 1.0)
        capture.reset(seed)
        gc.collect()
        jax.block_until_ready(self.params)

        compiles0, compile_s0 = clock.compiles, clock.seconds
        compile_log = CompileLog()
        jax_logger = logging.getLogger("jax")
        jax_logger.addHandler(compile_log)
        jax.config.update("jax_log_compiles", True)
        traced = TraceSlice() if self.trace else None
        capture.on = True
        spans.open()
        setup_s = time.perf_counter() - T_PROCESS
        if traced is not None:
            wr = window.drive(pod.server, due, seconds,
                              frame_base=frame_base, t_base=self.t_base,
                              spans=spans, at=(TRACE_FROM * seconds,
                                               traced.start))
            traced.stop()
        else:
            wr = window.drive(pod.server, due, seconds,
                              frame_base=frame_base, t_base=self.t_base)
        pod.server.open_loop_end()
        capture.on = False
        spans.close()
        jax.config.update("jax_log_compiles", False)
        jax_logger.removeHandler(compile_log)
        self.frame_base += max(d.frame_idx for d in due) + 1
        self.t_base += seconds + 1.0
        window_compiles = clock.compiles - compiles0
        stats = self.device.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        log(f"setup: {setup_s:.3f} s (compile {compile_s0:.3f} s over "
            f"{compiles0} programs, {clock.cache_hits} persistent-cache "
            f"hits, cache {self.cache_dir}); session "
            + ", ".join(f"{k} {v:.3f} s" for k, v in self.setup_phases))
        log(f"window: compiles inside {window_compiles} "
            f"({clock.seconds - compile_s0:.3f} s)")
        for line in compile_log.lines[:20]:
            log(f"window compile: {line}")

        # ---- what the window did ---------------------------------------
        arrivals = []
        for d in due:
            key = (d.stream, frame_base + d.frame_idx)
            arrivals.append({
                "due": wr.t0 + d.t_s, "handover": wr.handover.get(key),
                "finish": sink.finished.get(key),
                "verdict": sink.verdicts.get(key),
                "failed": key in wr.failed})
        attempted = sum(a["handover"] is not None for a in arrivals)
        missed = sum(a["verdict"] in ("missed", "reject") for a in arrivals)
        lost = sum(a["handover"] is not None and not a["failed"]
                   and a["verdict"] in ("admit", "degrade")
                   and a["finish"] is None for a in arrivals)
        failed = sum(a["failed"] for a in arrivals) + lost \
            + capture.malformed
        rounds = collections.Counter(
            a["handover"] for a in arrivals
            if a["verdict"] in ("admit", "degrade"))
        latencies = [(a["finish"] - a["due"]) if a["finish"] is not None
                     else math.inf for a in arrivals]
        finished = [a for a in arrivals if a["finish"] is not None
                    and a["finish"] <= wr.t_end]
        last = max((a["handover"] for a in arrivals if a["handover"]),
                   default=wr.t0)
        log(f"window: {len(due)} frames due, {attempted} handed over, "
            f"{len(finished)} finished in {wr.t_end - wr.t0:.3f} s, "
            f"{len(rounds)} rounds serving {list(rounds.values())} frames, "
            f"{missed} missed "
            f"or rejected, {lost} lost, {capture.malformed} malformed, "
            f"{len(wr.errors)} rounds raised; last handover "
            f"{last - wr.t0:.3f} s, loop ended {wr.t_end - wr.t0:.3f} s")
        for err in wr.errors[:2]:
            log(err)
        per_frame = sorted(len(s) for _, s, _ in capture.nms)
        if per_frame:
            log(f"window: detections per frame before NMS: min "
                f"{per_frame[0]}, median {per_frame[len(per_frame) // 2]}, "
                f"max {per_frame[-1]}; crop cache "
                f"{pod.backend.crop_cache_hits} hits, "
                f"{pod.backend.crop_cache_misses} misses (whole run)")

        device = self.device
        kind = device.device_kind
        dev = {"platform": device.platform, "kind": kind,
               "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
        run = Run(cell=cell, config=config, seconds=seconds, t0=wr.t0,
                  t_end=wr.t_end, arrivals=arrivals, frames=len(finished),
                  latencies_ms=[x * 1e3 for x in latencies],
                  spans=list(spans.records),
                  dispatches=[x for x in sink.dispatches
                              if wr.t0 <= x[0] <= wr.t_end],
                  forwards=list(capture.forwards),
                  flops=[forward_flops(d) for d in config["detectors"]],
                  trace=None, trace_t0=None, peak=None, setup_s=setup_s)
        metrics: dict = {}
        breakdown = None
        if traced is not None:
            run.peak = peak(kind) if device.platform == "tpu" else None
            t_read = time.perf_counter()
            raw = tracefile.load(traced.log_dir)
            run.trace = tracefile.reduce(raw)
            run.trace_t0 = traced.t0
            _rmtree(traced.log_dir)
            log(f"trace: {run.trace['window_s']:.3f} s window, busy "
                f"{run.trace['busy_s']:.3f} s, read in "
                f"{time.perf_counter() - t_read:.3f} s; planes "
                + "; ".join(f"{k}: " + ", ".join(
                    f"{ln} {len(ev)}" for ln, ev in v.items())
                    for k, v in raw["devices"].items())
                + f"; host spans {len(raw['spans'])}")
            dev["busy_s"] = run.trace["busy_s"]
            dev["window_s"] = run.trace["window_s"]
            breakdown = {"device_ops": run.trace["device_ops"],
                         "idle_gaps": run.trace["idle_gaps"]}
            wanted = cell["per_layer"]
        else:
            wanted = cell["end_to_end"]
        for m in wanted:
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        # ---- correct -----------------------------------------------------
        served, nms = capture.served(), list(capture.nms)
        capture.reset(seed)
        if free:
            del pod, capture, sink
            self.pod = self.capture = self.sink = self.params = None
        gc.collect()
        t_ref = time.perf_counter()
        params_ref = [ref.init_params(s, d) for s, d in
                      zip(config["weight_seeds"], config["detectors"])]
        numbers = check_mod.served_numbers(served, nms, config, seed,
                                           params_ref, log=log)
        self.last = None if free else (served, nms, params_ref)
        limits = config["limits"]
        log(f"reference: {numbers['_rows']} rows of {len(served)} "
            f"dispatches, {numbers['_nms_frames']} NMS frames "
            f"({numbers['_nms_edge']} on the IoU edge), "
            f"{time.perf_counter() - t_ref:.3f} s")
        correct = (check_mod.verdict(numbers, limits) and failed == 0
                   and numbers["_rows"] > 0)
        out = {"correct": correct, "attempted": attempted, "failed": failed,
               "metrics": metrics, "device": dev}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = {n: {"value": numbers[n], "limit": limits[n]}
                         for n in check_mod.NAMES}
        return out


class TraceSlice:
    """The profiler over the last part of a window, from the first
    handover ``TRACE_FROM`` of the way in to the window's end, marked on
    the trace by a ``tracefile.WINDOW`` annotation.  A whole window holds
    some hundred thousand program launches, and its trace outgrew a
    host's memory."""

    def __init__(self):
        self.log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = None
        self._window = None

    def start(self) -> None:
        import jax

        from bench import tracefile

        # the benchmark's spans are level-1 annotations; the runtime's
        # verbose host events and the programs' HLO are not read
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(tracefile.WINDOW)
        self._window.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        if self._window is None:
            raise RuntimeError("the window closed before the trace began")
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()


TRACE_FROM = 0.75


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             device=None) -> dict:
    """One run of ``cell`` (from :func:`load_cell`): the result line."""
    session = Session(cell, seed, trace, device=device)
    return session.window(seed, seconds, free=True)


def _rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "nothing was run")
    chips = cell["workload"]["chips"]
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}; nothing was run")
    peak(devices[0].device_kind)  # an unknown chip is an error up front
    print(f"device: {devices[0].device_kind} x{len(devices)}; workload "
          f"{args.workload}, seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   device=devices[0])
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
