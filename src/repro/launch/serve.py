"""Serving driver: ``python -m repro.launch.serve [...]``.

Multiplexes N synthetic 360-degree streams through the OmniSense pod
scheduler (the paper's pipeline as the pod's control plane) and prints
per-tick throughput / batching stats. ``--backend jax`` runs the real
detector ladder on rendered frames; the default oracle backend is the
calibrated fast path.

``--backend jax`` builds the pod with :func:`build_jax_pod`: the first
two rungs of the paper's ladder (``yolo-tiny-416``, ``yolo-csp-512``)
at their published input sizes and widths, 80 classes, seeded random
weights, the fused projection path, and rendered 960x1920 ERP frames.
It compiles through JAX's persistent cache (:func:`configure_compile_cache`):

    PYTHONPATH=src python -m repro.launch.serve --backend jax --streams 8 \
        --frames 4 --open-loop --jitter 0 --admission slo

``--policy {sync,deadline,async}`` picks the drain policy of the
event-clock serving runtime (``repro.serving.runtime``):

  * ``sync``     — the tick barrier (default; pre-runtime behaviour,
    bit-identical);
  * ``deadline`` — earliest-deadline / weighted-shortest-first
    cross-variant dispatch ordering over the streams' budgets;
  * ``async``    — residual sub-bucket chunks carry to the next tick
    while their replica group is busy, priced by the overlap model:

    PYTHONPATH=src python -m repro.launch.serve --streams 8 --devices 8 \
        --policy async

``--devices D`` partitions D VIRTUAL device slots into per-variant
replica groups (``repro.serving.placement``): the V per-variant
forwards are scheduled concurrently and the tick model switches to the
device-aware max-over-groups — priced by the calibrated latency model,
no accelerators consulted:

    PYTHONPATH=src python -m repro.launch.serve --streams 8 --devices 8

``--pod-allocate`` switches admission to the pod-level allocator
(``repro.serving.pod_allocation``): each tick the per-stream knapsacks
are coupled through amortized batched costs and per-group queue
depth/utilisation by a fixed-point loop.  Since the runtime refactor
this is a property of the POLICY (``SchedulePolicy(pod_allocate=True)``;
the transitional bare-flag DeprecationWarning was removed on schedule):

    PYTHONPATH=src python -m repro.launch.serve --streams 8 --devices 8 \
        --policy sync --pod-allocate

``--open-loop`` (PR 6) feeds the pod arrival-clocked OPEN-LOOP traffic
(``repro.serving.traffic``) instead of the closed-loop frame barrier:
each stream's camera ticks at ``--fps`` with seeded lognormal
``--jitter``, a frame whose predecessor still occupies the depth-1
camera buffer is counted missed (never fabricated), and every arrival
passes the policy's admission hook against the ``--slo`` envelope —
``--admission slo`` degrades or rejects when the projected queueing
load would blow it:

    PYTHONPATH=src python -m repro.launch.serve --streams 8 \
        --open-loop --fps 0.5 --jitter 0.2 --slo 2.0 --admission slo

``--pods P`` serves the open-loop traffic through the FLEET tier
(``repro.serving.fleet``): P pods behind a ``--routing`` stream router
(sticky ``least-loaded`` balance, or ``affinity`` consistent hashing
so co-variant streams co-locate and batch), with ``--devices`` split
per pod by ``serving_scale_plan``:

    PYTHONPATH=src python -m repro.launch.serve --streams 32 \
        --open-loop --fps 0.5 --slo 2.0 --admission slo \
        --devices 8 --pods 4 --routing affinity

The REAL shard_map-sharded detector path is exercised by
``benchmarks/serving_bench.py --devices 8`` and the `multidevice` test
lane (both force fake host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

import numpy as np

from repro.core.omnisense import OmniSenseLoop
from repro.data.synthetic import make_video, render_erp
from repro.models import detector as det_mod
from repro.serving import profiles
from repro.serving.batching import ShapeBuckets
from repro.serving.network import NetworkModel
from repro.serving.runtime import make_policy
from repro.serving.scheduler import (JaxDetectorBackend,
                                     OmniSenseLatencyModel, OracleBackend)
from repro.serving.server import PodServer

REPO_ROOT = Path(__file__).resolve().parents[3]

# The real-backend pod: the first two rungs of the paper's ladder at
# their published input sizes and widths (80 classes), and the ERP size
# its frames are rendered at.  A whole 4K ERP is stacked per crop by the
# fused projection, so frames stay at 960x1920 until that is fixed.
JAX_POD_DETECTORS = det_mod.PAPER_LADDER[:2]
JAX_POD_ERP_HW = (960, 1920)


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX.  Otherwise
    the cache lives at ``<repo>/.jax_cache``: the path is part of the
    cache key, so it must not move between runs.  Call once, before the
    first compile.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class RenderedFrames:
    """``frame_source`` of rendered ERP frames, one synthetic video per
    stream.  The newest frame of each stream is kept, so repeated calls
    for one frame return the same array (the crop cache keys on it)."""

    def __init__(self, videos, height: int, width: int):
        self.videos = videos
        self.height, self.width = height, width
        self._last: dict[int, tuple[int, np.ndarray]] = {}

    def __call__(self, stream: int, frame: int) -> np.ndarray:
        hit = self._last.get(stream)
        if hit is None or hit[0] != frame:
            hit = self._last[stream] = (frame, render_erp(
                self.videos[stream], frame, self.height, self.width))
        return hit[1]


def build_jax_pod(n_streams: int, frames: int, *,
                  buckets: ShapeBuckets | None = None, budget_s: float = 1.8,
                  bandwidth_mbps: float = 17.9, policy=None, devices=None,
                  telemetry=None) -> tuple[PodServer, JaxDetectorBackend]:
    """The detector pod on the real JAX backend (see module docstring).

    Weights are ``init_params`` on seeds 0 and 1.  ``devices`` (two or
    more real devices) splits them into per-variant replica groups of
    equal size whose forwards ``shard_map`` over the group; None serves
    on the default device.
    """
    import jax

    cfgs = list(JAX_POD_DETECTORS)
    init = jax.jit(det_mod.init_params, static_argnums=1)
    params = [init(jax.random.PRNGKey(i), c) for i, c in enumerate(cfgs)]
    buckets = buckets or ShapeBuckets(
        resolutions=tuple(sorted({c.input_size for c in cfgs})))
    # random weights score near 1/80, so a low threshold keeps detections
    backend = JaxDetectorBackend(cfgs, params, conf=0.005, use_kernel=False,
                                 max_det=16, buckets=buckets)
    variants = profiles.make_ladder()[:len(cfgs)]
    lat = OmniSenseLatencyModel(profiles.paper_profile(),
                                NetworkModel(bandwidth_mbps))
    costs = [lat._pre(v) + lat._inf(v) for v in variants]
    videos = [make_video(n_frames=frames + 8, n_objects=30 + 5 * (s % 4),
                         seed=100 + s) for s in range(n_streams)]
    loops = [OmniSenseLoop(variants, lat, backend, budget_s=budget_s,
                           explore_costs=costs) for _ in range(n_streams)]
    placement = None
    if devices is not None and len(devices) > 1:
        from repro.serving.placement import VariantPlacement

        placement = VariantPlacement(variants, devices=devices,
                                     cost_fn=lambda v: 1.0)
    server = PodServer(loops, [backend] * n_streams,
                       max_batch=buckets.max_batch, buckets=buckets,
                       frame_source=RenderedFrames(videos, *JAX_POD_ERP_HW),
                       placement=placement, policy=policy,
                       telemetry=telemetry)
    return server, backend


def _oracle_streams(args):
    """The oracle pod's streams: ``(variants, loops, backends, cost_fn)``
    for the ``--tasks`` mix."""
    if args.tasks != "detection":
        from repro.serving import tasks as task_registry

        stream_tasks = task_registry.stream_tasks_for(args.tasks,
                                                      args.streams)
        videos = [make_video(n_frames=args.frames + 8,
                             n_objects=30 + 5 * (s % 4), seed=100 + s)
                  for s in range(args.streams)]
        return task_registry.build_task_streams(
            stream_tasks, videos, [args.budget] * args.streams)
    variants = profiles.make_ladder()
    lat = OmniSenseLatencyModel(profiles.paper_profile(),
                                NetworkModel(args.bandwidth_mbps))
    costs = [lat._pre(v) + lat._inf(v) for v in variants]
    loops, backends = [], []
    for s in range(args.streams):
        video = make_video(n_frames=args.frames + 8,
                           n_objects=30 + 5 * (s % 4), seed=100 + s)
        backend = OracleBackend(video)
        backends.append(backend)
        loops.append(OmniSenseLoop(variants, lat, backend,
                                   budget_s=args.budget,
                                   explore_costs=costs))
    return variants, loops, backends, lat._inf


def _serve_fleet(args, variants, loops, backends, cost_fn,
                 telemetry) -> None:
    """``--pods``: serve open-loop traffic through a FleetServer of
    oracle pods and print its report."""
    from repro.distributed.elastic import serving_scale_plan
    from repro.serving.fleet import FleetServer, format_fleet_report
    from repro.serving.traffic import ArrivalProcess

    per_pod = serving_scale_plan(args.devices, args.pods)["per_pod_devices"]

    def make_pod(pod_id: int) -> PodServer:
        pod_placement = None
        if per_pod > 0:
            from repro.serving.placement import VariantPlacement

            pod_placement = VariantPlacement.virtual(
                variants, per_pod, cost_fn=cost_fn)
        pol = make_policy(args.policy or "sync",
                          pod_allocate=args.pod_allocate,
                          admission=args.admission)
        return PodServer(loops, backends, max_batch=args.max_batch,
                         placement=pod_placement, policy=pol)

    fleet = FleetServer(make_pod, args.pods, routing=args.routing,
                        telemetry=telemetry)
    horizon_s = args.frames / args.fps
    traffic = ArrivalProcess(args.streams, fps=args.fps,
                             jitter=args.jitter, seed=0,
                             horizon_s=horizon_s)
    fstats = fleet.run_open_loop(traffic, slo_s=args.slo)
    if telemetry is not None:
        telemetry.close()
        print(f"telemetry event log: {args.events}")
    for line in format_fleet_report(fstats, horizon_s):
        print(line)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--budget", type=float, default=1.8)
    ap.add_argument("--bandwidth-mbps", type=float, default=17.9)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="partition this many device slots into per-variant "
                         "replica groups (0 = single-device pod)")
    ap.add_argument("--policy", choices=("sync", "deadline", "async"),
                    default=None,
                    help="drain policy of the serving runtime "
                         "(repro.serving.runtime; default sync — the "
                         "pre-runtime tick barrier, bit-identical)")
    ap.add_argument("--pod-allocate", action="store_true",
                    help="couple the per-stream knapsacks through batched "
                         "costs and group utilisation (the fixed-point "
                         "pod-level allocator; an admission property of "
                         "the --policy object since the runtime refactor)")
    ap.add_argument("--open-loop", action="store_true",
                    help="feed arrival-clocked open-loop traffic "
                         "(repro.serving.traffic) instead of the "
                         "closed-loop frame barrier: per-stream fps "
                         "clocks, depth-1 camera buffer, admission "
                         "control, SLO goodput accounting")
    ap.add_argument("--fps", type=float, default=0.5,
                    help="per-stream arrival rate for --open-loop")
    ap.add_argument("--jitter", type=float, default=0.2,
                    help="lognormal sigma on open-loop inter-arrival times")
    ap.add_argument("--slo", type=float, default=2.0,
                    help="end-to-end SLO for open-loop goodput accounting")
    ap.add_argument("--admission", choices=("admit-all", "slo"),
                    default="admit-all",
                    help="open-loop admission policy: admit everything, or "
                         "degrade/reject when projected load exceeds the "
                         "SLO envelope")
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="write the structured JSONL telemetry event log "
                         "here (repro.serving.telemetry; inspect with "
                         "python -m repro.launch.replay report PATH)")
    ap.add_argument("--pods", type=int, default=0,
                    help="serve through a FleetServer of this many pods "
                         "(repro.serving.fleet; requires --open-loop; "
                         "--devices is the FLEET-wide budget split per "
                         "pod; 0 = the single-pod server)")
    ap.add_argument("--routing", choices=("least-loaded", "affinity"),
                    default="least-loaded",
                    help="fleet stream-routing policy (with --pods): "
                         "sticky least-loaded balance, or consistent "
                         "hashing on content affinity so co-variant "
                         "streams batch together")
    ap.add_argument("--tasks", choices=("detection", "action", "mixed"),
                    default="detection",
                    help="analytics task mix (repro.serving.tasks "
                         "registry): homogeneous detection (default, "
                         "honours --bandwidth-mbps), homogeneous "
                         "action recognition, or an alternating mixed "
                         "pod whose two variant ladders share one "
                         "capacity envelope")
    ap.add_argument("--backend", choices=("oracle", "jax"), default="oracle",
                    help="inference backend: the calibrated oracle "
                         "(default), or the real JAX detector pod of "
                         "build_jax_pod on rendered frames (detection "
                         "only, one pod; --devices picks real devices)")
    args = ap.parse_args()
    if args.backend == "jax" and (args.pods or args.tasks != "detection"):
        ap.error("--backend jax serves one detection pod (no --pods, "
                 "--tasks detection)")
    if args.pods and not args.open_loop:
        ap.error("--pods requires --open-loop (the fleet tier serves "
                 "arrival-clocked traffic)")
    policy = make_policy(args.policy or "sync",
                         pod_allocate=args.pod_allocate,
                         admission=args.admission if args.open_loop
                         else None)

    telemetry = None
    if args.events:
        from repro.serving.telemetry import JsonlSink

        telemetry = JsonlSink(args.events)

    if args.backend == "jax":
        import jax

        if len(jax.devices()) < args.devices:
            ap.error(f"--devices {args.devices}: JAX sees "
                     f"{len(jax.devices())} devices")
        print(f"compile cache: {configure_compile_cache()}")
        server, backend = build_jax_pod(
            args.streams, args.frames, buckets=ShapeBuckets.for_max_batch(
                args.max_batch, resolutions=tuple(sorted(
                    {c.input_size for c in JAX_POD_DETECTORS}))),
            budget_s=args.budget, bandwidth_mbps=args.bandwidth_mbps,
            policy=policy, telemetry=telemetry,
            devices=jax.devices()[:args.devices] if args.devices else None)
        print(f"jax pod on {jax.devices()[0].device_kind}: "
              + ", ".join(f"{c.name}@{c.input_size}px"
                          for c in JAX_POD_DETECTORS)
              + f", ERP {JAX_POD_ERP_HW[0]}x{JAX_POD_ERP_HW[1]}")
    else:
        backend = None
        variants, loops, backends, cost_fn = _oracle_streams(args)
        if args.pods > 0:
            _serve_fleet(args, variants, loops, backends, cost_fn, telemetry)
            return
        placement = None
        if args.devices > 0:
            from repro.serving.placement import VariantPlacement

            placement = VariantPlacement.virtual(variants, args.devices,
                                                 cost_fn=cost_fn)
        server = PodServer(loops, backends, max_batch=args.max_batch,
                           placement=placement, policy=policy,
                           telemetry=telemetry)
    horizon_s = None
    if args.open_loop:
        from repro.serving.traffic import ArrivalProcess

        horizon_s = args.frames / args.fps
        traffic = ArrivalProcess(args.streams, fps=args.fps,
                                 jitter=args.jitter, seed=0,
                                 horizon_s=horizon_s)
        stats = server.run_open_loop(traffic, slo_s=args.slo)
    else:
        stats = server.run(range(args.frames))
    if telemetry is not None:
        telemetry.close()
        print(f"telemetry event log: {args.events}")
    print(f"served {stats.frames} frames across {args.streams} streams "
          f"[{stats.policy} policy]")
    print(f"detections: {stats.total_detections}  "
          f"mean plan latency: {stats.mean_e2e:.2f}s (budget {args.budget}s)")
    if policy.pod_allocate:
        from repro.serving.server import format_pod_allocation_report

        print(format_pod_allocation_report(stats))
    if len(server.tasks) > 1:
        per = ", ".join(
            f"{t}: {stats.frames_by_task.get(t, 0)} frames, "
            f"proxy {p:.3f}"
            for t, p in stats.accuracy_proxy_by_task.items())
        print(f"per-task ({'+'.join(server.tasks)} pod): {per}")
    print(f"control-plane overhead: "
          f"{1e3 * stats.sum_overhead / stats.frames:.2f} ms/frame")
    if stats.batch_sizes:
        print(f"variant batches: mean={stats.mean_batch:.2f} "
              f"p95={int(np.percentile(stats.batch_sizes, 95))}")
    print(f"batched dispatches: {stats.dispatches}  "
          f"inference gain: {stats.batching_gain:.2f}x "
          f"({stats.sum_batched_inf_s:.1f}s batched vs "
          f"{stats.sum_per_request_inf_s:.1f}s per-request)")
    pct = stats.event_e2e_percentiles()
    print(f"event-clock tick: mean={stats.mean_tick:.3f}s  "
          f"E2E p50/p95/p99={pct[50]:.2f}/{pct[95]:.2f}/{pct[99]:.2f}s  "
          f"carried requests: {stats.carried_requests} "
          f"({stats.carry_tick_slots} request-ticks)")
    if server.placement is not None:
        from repro.serving.server import format_group_report

        for line in format_group_report(stats, server.placement):
            print(line)
    if horizon_s is not None:
        from repro.serving.server import format_open_loop_report

        for line in format_open_loop_report(stats, horizon_s):
            print(line)
    if backend is not None:
        from repro.core.sphere import nms_device_trace_count

        print(f"jit traces: detector {backend.trace_count}  "
              f"device NMS {nms_device_trace_count()}")


if __name__ == "__main__":
    main()
