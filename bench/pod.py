"""The served system: the detector pod of one configuration file.

The pod is built through the program's public constructors, as
``repro.launch.serve.build_jax_pod`` builds it: ``JaxDetectorBackend``
(fused projection, ``use_kernel=False``), one ``OmniSenseLoop`` per
stream over the calibrated ladder's first rungs, and a ``PodServer``
under the ``sync`` policy with admit-all admission.  The only part of
the pod that is the benchmark's own is the camera: frames are rendered
once per run into a small per-stream pool (:class:`PooledFrames`), so
rendering is set-up and never window time.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import numpy as np


def detector_configs(config: dict):
    """The configuration's ``DetectorConfig`` ladder."""
    from repro.models import detector as det_mod

    return [det_mod.DetectorConfig(**d) for d in config["detectors"]]


def init_weights(config: dict):
    """Every rung's weights in one jitted call on the default device:
    ``init_params(PRNGKey(seed), cfg)`` for the file's weight seeds."""
    import jax

    from repro.models import detector as det_mod

    cfgs = detector_configs(config)
    seeds = config["weight_seeds"]

    @jax.jit
    def init():
        return [det_mod.init_params(jax.random.PRNGKey(s), c)
                for s, c in zip(seeds, cfgs)]

    params = init()
    jax.block_until_ready(params)
    return params


def make_videos(config: dict):
    from repro.data.synthetic import make_video

    v = config["video"]
    return [make_video(n_frames=config["frame_pool"] + v["extra_frames"],
                       n_objects=v["n_objects_base"] + v["n_objects_step"]
                       * (s % v["n_objects_period"]),
                       seed=v["seed_base"] + s)
            for s in range(config["streams"])]


def render_pool(config: dict, videos, workers: int = 8):
    """``pool[stream][k]``: frame ``k`` of each stream's video, rendered
    at the configuration's ERP size, in parallel threads."""
    from repro.data.synthetic import render_erp

    h, w = config["erp_hw"]
    jobs = [(s, k) for s in range(len(videos))
            for k in range(config["frame_pool"])]
    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        frames = list(ex.map(
            lambda job: render_erp(videos[job[0]], job[1], h, w), jobs))
    pool = [[None] * config["frame_pool"] for _ in videos]
    for (s, k), f in zip(jobs, frames):
        pool[s][k] = f
    return pool


class PooledFrames:
    """``frame_source`` over a rendered pool.  Frame ``f`` of a stream is
    a fresh copy of ``pool[stream][f % K]``; the newest frame of each
    stream is kept, so the calls for one frame return the same array,
    as ``repro.launch.serve.RenderedFrames`` does (the crop cache keys
    on the array and guards on its content, so a copy never aliases
    another frame's crops)."""

    def __init__(self, pool):
        self.pool = pool
        self._last: dict[int, tuple[int, np.ndarray]] = {}

    def __call__(self, stream: int, frame: int) -> np.ndarray:
        hit = self._last.get(stream)
        if hit is None or hit[0] != frame:
            frames = self.pool[stream]
            hit = self._last[stream] = (frame,
                                        frames[frame % len(frames)].copy())
        return hit[1]


@dataclasses.dataclass
class Pod:
    server: object
    backend: object
    loops: list
    frames: PooledFrames
    buckets: object


def build_pod(config: dict, params, frames: PooledFrames,
              telemetry=None) -> Pod:
    """The pod of ``config`` over ``params`` (from :func:`init_weights`)."""
    from repro.core.omnisense import OmniSenseLoop
    from repro.serving import profiles
    from repro.serving.batching import ShapeBuckets
    from repro.serving.network import NetworkModel
    from repro.serving.runtime import make_policy
    from repro.serving.scheduler import (JaxDetectorBackend,
                                         OmniSenseLatencyModel)
    from repro.serving.server import PodServer

    cfgs = detector_configs(config)
    buckets = ShapeBuckets(
        tuple(config["batch_sizes"]),
        resolutions=tuple(sorted({c.input_size for c in cfgs})),
        nms_sizes=tuple(config["nms_sizes"]))
    backend = JaxDetectorBackend(
        cfgs, params, conf=config["conf"], use_kernel=config["use_kernel"],
        max_det=config["max_det"], buckets=buckets, fused=config["fused"],
        crop_cache_size=config["crop_cache_size"])
    variants = profiles.make_ladder()[:len(cfgs)]
    if [v.name for v in variants] != [c.name for c in cfgs]:
        raise ValueError(f"detectors {[c.name for c in cfgs]} are not the "
                         f"ladder's first rungs {[v.name for v in variants]}")
    lat = OmniSenseLatencyModel(profiles.paper_profile(),
                                NetworkModel(config["bandwidth_mbps"]))
    costs = [lat._pre(v) + lat._inf(v) for v in variants]
    n = config["streams"]
    loops = [OmniSenseLoop(variants, lat, backend,
                           budget_s=config["budget_s"], explore_costs=costs,
                           nms_threshold=config["nms_threshold"])
             for _ in range(n)]
    admission = config["admission"]
    policy = make_policy(config["policy"],
                         admission=None if admission == "admit-all"
                         else admission)
    server = PodServer(loops, [backend] * n, max_batch=buckets.max_batch,
                       buckets=buckets, frame_source=frames,
                       policy=policy, telemetry=telemetry)
    return Pod(server, backend, loops, frames, buckets)
