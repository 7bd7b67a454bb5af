#!/usr/bin/env python3
"""Chip smoke: serve the real detector pod on a TPU and check its outputs.

    python chip_smoke.py            # one chip: the served pod + references
    python chip_smoke.py --chips 4  # replica groups over four chips

The one-chip run drives ``PodServer.run_open_loop`` with SLO admission
over the pod of ``repro.launch.serve.build_jax_pod`` (yolo-tiny-416 and
yolo-csp-512 at their published sizes, 80 classes, seeded random
weights, 8 streams of rendered 960x1920 ERP frames), then checks:

  * the detector and the device NMS compiled (jit trace counts > 0),
    the pod emitted detections, and the device NMS program holds the
    Pallas kernel (``tpu_custom_call``), not an interpret-mode fallback;
  * the raw head outputs of each variant's served batch-8 program on the
    chip against the same forward on the host CPU at
    ``precision=HIGHEST``, within ``FLOOR_FACTOR`` times the error of a
    host forward computed in bf16;
  * one batched SphIoU from the chip against the float64 host IoU
    (``IOU_TOL``).

``--chips 4`` runs only the multi-device path: one pod whose replica
groups span four devices (two groups of two, ``shard_map`` forwards)
serves the same traffic, every device must have served a sharded
forward and hold arrays, and each group's sharded program must give
the raw heads of the one-device program on one batch of crops.

It runs in one process and starts none.  Without a TPU it exits non-zero
before doing any work.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STREAMS = 8
FRAMES = 4          # per stream: open-loop horizon FRAMES / FPS
FPS = 0.5           # per-stream camera rate (event clock)
SLO_S = 2.0
BATCH_SIZES = (1, 8)
NMS_SIZES = (64, 128, 256)
# Raw detector heads are compared as max |x - ref| / max |ref|, worst of
# the head scales.  The chip's default f32 convolutions round their
# operands to bf16, and this network (random weights, ~20 convolutions,
# each renormalised by GroupNorm) carries that rounding to the heads:
# on the smoke's crops the same forward computed in bf16 on the host is
# 6e-2 to 9e-2 off f32, while a wrong weight, row order or shard is off
# by 1.0 or more.  So a chip forward may be FLOOR_FACTOR times that
# host bf16 floor, measured on the same crops, off its reference.
FLOOR_FACTOR = 2.0
# SphIoU, max abs error against float64.  The kernel works in f32: its
# trig and atan2 carry ~1e-7 rad error, which the IoU of boxes no
# smaller than 0.05 rad turns into a few 1e-6.
IOU_TOL = 1e-5


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} chips asked for, JAX sees "
                 f"{len(devices)}; nothing was run")
    return devices


class CompileClock:
    """Seconds spent in XLA compilation (or loading a cached program)
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_pod(devices=None):
    from repro.launch.serve import JAX_POD_DETECTORS, build_jax_pod
    from repro.serving.batching import ShapeBuckets
    from repro.serving.runtime import make_policy

    buckets = ShapeBuckets(
        BATCH_SIZES, resolutions=tuple(sorted(
            {c.input_size for c in JAX_POD_DETECTORS})),
        nms_sizes=NMS_SIZES)
    return build_jax_pod(STREAMS, FRAMES, buckets=buckets,
                         policy=make_policy("sync", admission="slo"),
                         devices=devices)


def serve(server):
    """Serve ``FRAMES`` frames per stream of open-loop arrivals with SLO
    admission, all cameras in step (no jitter)."""
    from repro.serving.traffic import ArrivalProcess

    t0 = time.perf_counter()
    stats = server.run_open_loop(
        ArrivalProcess(STREAMS, fps=FPS, jitter=0.0, seed=0,
                       horizon_s=FRAMES / FPS), slo_s=SLO_S)
    return stats, time.perf_counter() - t0


def nms_program_text(n: int) -> str:
    """Compiled HLO of the served device-NMS program at (STREAMS, n)."""
    import jax
    import jax.numpy as jnp

    from repro.core.sphere import _sph_nms_batch_device

    f32 = jnp.float32
    return _sph_nms_batch_device.lower(
        jax.ShapeDtypeStruct((STREAMS, n, 4), f32),
        jax.ShapeDtypeStruct((STREAMS, n), f32),
        jax.ShapeDtypeStruct((STREAMS, n), jnp.bool_),
        jax.ShapeDtypeStruct((), f32),
        interpret=False, use_pallas=True).compile().as_text()


def served_crops(server, size: int):
    """One top-rung batch of crops, one per stream, projected by the
    served projection program from each stream's first frame."""
    from repro.kernels.gnomonic.ops import project_srois_batched

    b = BATCH_SIZES[-1]
    return project_srois_batched(
        [server.frame_source(s % STREAMS, 0) for s in range(b)],
        [(0.7 * k - 2.5, 0.3 * (k % 3) - 0.3) for k in range(b)],
        [(1.0, 1.0)] * b, (size, size))


def served_heads(backend, idx: int, imgs, group=None):
    """Raw heads of the compiled program the pod serves this batch with
    (``group``: a replica group's ``shard_map`` program)."""
    import jax.numpy as jnp

    b = imgs.shape[0]
    *_, heads = backend._batched_fn(idx, b, group)(
        backend._params_for(idx, group), imgs, jnp.ones((b,), bool))
    return heads


def heads_error(got, want) -> float:
    """max |got - want| over max |want|, worst of the head scales."""
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(g) - np.asarray(w)))
                     / np.max(np.abs(np.asarray(w))))
               for g, w in zip(got, want))


def host_heads(backend, idx: int, imgs, dtype=None):
    """Raw heads of variant ``idx`` on the host CPU at HIGHEST precision,
    computed in ``dtype`` (default: the variant's own, f32)."""
    import dataclasses

    import jax
    import numpy as np

    from repro.models import detector as det_mod

    cfg = backend.cfgs[idx]
    if dtype is not None:
        cfg = dataclasses.replace(cfg, compute_dtype=dtype)
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        return jax.jit(det_mod.apply, static_argnums=2)(
            jax.device_put(backend.params[idx], cpu),
            jax.device_put(np.asarray(imgs), cpu), cfg)


def rounding_floor(backend, idx: int, imgs):
    """``(f32 heads, floor)``: the host f32 forward of ``imgs`` and how
    far the same forward computed in bf16 is from it."""
    import jax.numpy as jnp

    want = host_heads(backend, idx, imgs)
    return want, heads_error(host_heads(backend, idx, imgs, jnp.bfloat16),
                             want)


def forward_error(server, backend) -> list[tuple[float, float]]:
    """Per variant: ``(error, floor)`` of the raw heads of its served
    top-rung program on the chip against the host f32 forward."""
    out = []
    for idx, cfg in enumerate(backend.cfgs):
        imgs = served_crops(server, cfg.input_size)
        want, floor = rounding_floor(backend, idx, imgs)
        out.append((heads_error(served_heads(backend, idx, imgs), want),
                    floor))
    return out


def sphiou_error(seed: int = 0) -> float:
    """Max |chip SphIoU - float64 host SphIoU| on a (STREAMS, 64) batch."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sphere import sph_iou_matrix_np
    from repro.kernels.sphiou.ops import sphiou_matrix_batch

    rng = np.random.default_rng(seed)
    shape = (STREAMS, NMS_SIZES[0])
    boxes = np.stack([rng.uniform(-np.pi, np.pi, shape),
                      rng.uniform(-1.4, 1.4, shape),
                      rng.uniform(0.05, 0.9, shape),
                      rng.uniform(0.05, 0.9, shape)], -1).astype(np.float32)
    got = np.asarray(sphiou_matrix_batch(jnp.asarray(boxes),
                                         jnp.asarray(boxes)))
    want = sph_iou_matrix_np(boxes.astype(np.float64),
                             boxes.astype(np.float64))
    return float(np.max(np.abs(got - want)))


def report_served(stats, wall: float) -> None:
    print(f"served: {STREAMS} streams, {stats.arrivals} arrivals "
          f"({stats.degraded} degraded, {stats.rejected} rejected, "
          f"{stats.missed} missed), {stats.frames} frames, "
          f"{stats.total_detections} detections, "
          f"{stats.dispatches} batched dispatches in {wall:.3f} s wall "
          f"(compiles included)")
    check(stats.total_detections > 0, "the pod emitted no detections")


def one_chip(clock, cache_dir: str) -> None:
    from repro.core.sphere import nms_device_trace_count
    from repro.launch.serve import JAX_POD_ERP_HW

    server, backend = make_pod()
    print("serving ...")
    print("variants: " + ", ".join(f"{c.name}@{c.input_size}px/"
                                   f"{c.n_classes}cls" for c in backend.cfgs)
          + f"  ERP {JAX_POD_ERP_HW[0]}x{JAX_POD_ERP_HW[1]}")
    stats, wall = serve(server)
    report_served(stats, wall)
    print(f"compile: {clock.seconds:.3f} s, {clock.cache_hits} "
          f"persistent-cache hits, cache dir {cache_dir}")
    print(f"traces: JaxDetectorBackend.trace_count={backend.trace_count} "
          f"nms_device_trace_count={nms_device_trace_count()}")
    check(backend.trace_count > 0, "the detector forward never compiled")
    check(nms_device_trace_count() > 0, "no tick took the device NMS path")

    kernel = "tpu_custom_call" in nms_program_text(NMS_SIZES[0])
    print(f"device NMS program holds tpu_custom_call: {kernel}")
    check(kernel, "the device NMS program has no Pallas kernel")

    traces = backend.trace_count
    errs = forward_error(server, backend)
    check(backend.trace_count == traces,
          "the reference ran a program the pod did not serve")
    for cfg, (err, floor) in zip(backend.cfgs, errs):
        print(f"reference: {cfg.name} served B={BATCH_SIZES[-1]} program, "
              f"raw heads vs CPU HIGHEST: {err:.3e} (host bf16 floor "
              f"{floor:.3e}, tol {FLOOR_FACTOR * floor:.3e})")
        check(err <= FLOOR_FACTOR * floor,
              f"{cfg.name} forward is off the reference")
    err = sphiou_error()
    print(f"reference: SphIoU vs float64 host: {err:.3e} (tol {IOU_TOL:g})")
    check(err <= IOU_TOL, "SphIoU is off the float64 reference")


def four_chips(devices) -> None:
    """Serve the pod placed over four devices, then check each replica
    group's sharded program against the one-device program on the same
    batch of crops: each row runs the same forward, split four rows per
    device instead of eight on one, so the raw heads may differ only by
    rounding (each is within about the bf16 floor of f32)."""
    server, backend = make_pod(devices=devices[:4])
    groups = server.placement.groups
    print("replica groups: " + "; ".join(
        f"{'+'.join(g.variants)} on devices "
        f"{[d.id for d in g.devices]}" for g in groups))
    check(len(groups) == 2 and all(g.n_devices == 2 for g in groups),
          "expected two replica groups of two devices")
    stats, wall = serve(server)
    report_served(stats, wall)
    sharded = {d for key in backend._jit_cache if len(key) == 3
               for d in key[2]}
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices[:4]]
    print(f"devices that served a sharded forward: {sorted(sharded)}; "
          f"bytes_in_use per device: {in_use}")
    check(sharded == {d.id for d in devices[:4]},
          "a device served no sharded forward")
    check(all(b > 0 for b in in_use), "a device holds no arrays")

    index = {c.name: i for i, c in enumerate(backend.cfgs)}
    for g in groups:
        for name in g.variants:
            idx = index[name]
            imgs = served_crops(server, backend.cfgs[idx].input_size)
            err = heads_error(served_heads(backend, idx, imgs, g),
                              served_heads(backend, idx, imgs))
            _, floor = rounding_floor(backend, idx, imgs)
            print(f"{name}: raw heads, {g.n_devices}-device shard_map "
                  f"program vs one device: {err:.3e} (host bf16 floor "
                  f"{floor:.3e}, tol {FLOOR_FACTOR * floor:.3e})")
            check(err <= FLOOR_FACTOR * floor,
                  f"{name}: the sharded forward is off the one-device one")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)  # progress survives a kill
    t_start = time.perf_counter()

    devices = require_tpu(args.chips)
    from repro.launch.serve import configure_compile_cache

    cache_dir = configure_compile_cache()
    clock = CompileClock()
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}")
    try:
        if args.chips == 4:
            four_chips(devices)
            print(f"compile: {clock.seconds:.3f} s, {clock.cache_hits} "
                  f"persistent-cache hits, cache dir {cache_dir}")
        else:
            one_chip(clock, cache_dir)
    except Failed as e:
        sys.exit(f"chip_smoke FAILED: {e}")
    print(f"total: {time.perf_counter() - t_start:.3f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
