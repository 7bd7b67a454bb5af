"""Latency model + inference backends + the OmniSense scheduler glue.

``OmniSenseLatencyModel`` computes the allocator's (d_pre, d_inf)
matrices exactly as section IV-C specifies:

    d_pre[i][j] = projection(PI at model i's input size)
                  + encode(same) if model i runs remotely
    d_inf[i][j] = delivery(PI bytes) if remote else 0
                  + model i's profiled inference time

Row 0 is the zero-cost "skip" pseudo-model.  Delivery delays come from
the passive profiler (omega-window) scaled by payload size, and the
projection/encode terms from the offline stage-cost profile — the PI
resolution always equals the allocated model's input size ("to avoid
resizing the image").

Backends:
  * ``OracleBackend`` — samples detections from the scene ground truth
    using each variant's gav as hit probability (+ box jitter, rare
    false positives).  Drives the reproduction benchmark (DESIGN.md
    section 7: no pretrained weights exist, the systems claim is about
    allocation given a ladder).
  * ``JaxDetectorBackend`` — really projects the SRoI (Pallas gnomonic
    kernel) and runs the JAX detector ladder; used by examples/tests.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.core import accuracy as acc_mod
from repro.core import sroi as sroi_mod
from repro.core.sphere import pi_box_to_sphbb
from repro.data.synthetic import SyntheticVideo
from repro.serving.network import NetworkModel, PassiveProfiler
from repro.serving.profiles import StageCosts
from repro.serving.telemetry import TelemetrySink


class OmniSenseLatencyModel:
    def __init__(self, costs: StageCosts, network: NetworkModel,
                 profiler: PassiveProfiler | None = None,
                 batch_marginal: float = 0.15,
                 pre_batch_marginal: float = 0.35):
        self.costs = costs
        self.network = network
        # a defaulted profiler inherits the link's RTT floor so its
        # payload rescaling never shrinks the fixed round-trip term
        self.profiler = profiler or PassiveProfiler(rtt_s=network.rtt_s)
        # marginal cost of each item beyond the first in a batched
        # forward (the standard sub-linear batching curve)
        self.batch_marginal = batch_marginal
        # same curve for the mobile-side projection/encode stage —
        # shallower batching than the edge forward (the mobile SoC
        # pipelines crops but streams encode mostly serially)
        self.pre_batch_marginal = pre_batch_marginal

    def _pre(self, variant: acc_mod.ModelProfile) -> float:
        mpix = variant.input_size ** 2 / 1e6
        t = self.costs.project_s_per_mpix * mpix
        if variant.location != "device":
            t += self.costs.encode_s_per_mpix * mpix
        return t

    def _inf(self, variant: acc_mod.ModelProfile) -> float:
        t = variant.infer_s
        if variant.location != "device":
            n_bytes = variant.input_size ** 2 * self.costs.bytes_per_pixel
            est = self.profiler.estimate(variant.name)
            if est == self.profiler.initial_s:
                t += self.network.delivery_delay(n_bytes)
            else:
                t += est
        return t

    def delays(self, srois: Sequence[sroi_mod.SRoI],
               variants: Sequence[acc_mod.ModelProfile]):
        r = len(srois)
        m = len(variants)
        d_pre = np.zeros((1 + m, r))
        d_inf = np.zeros((1 + m, r))
        for i, var in enumerate(variants):
            d_pre[1 + i, :] = self._pre(var)
            d_inf[1 + i, :] = self._inf(var)
        return d_pre, d_inf

    def batched_inference_delay(self, variant: acc_mod.ModelProfile,
                                batch_size: int) -> float:
        """Cost of ONE batched forward serving ``batch_size`` PIs.

        Per-batch fixed cost (the b=1 forward: dispatch, weight
        streaming and — for remote variants — the bundled payload
        delivery) plus a ``batch_marginal`` fraction of it for every
        additional item.  ``batch_size == 1`` reduces exactly to the
        per-request :meth:`_inf` term, so the allocator's utility
        ordering (which prices requests individually) is unchanged by
        the batched serving path; the pod server charges this instead
        of summing ``_inf`` per request.
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        return self._inf(variant) * (
            1.0 + (batch_size - 1) * self.batch_marginal)

    def amortized_inference_delay(self, variant: acc_mod.ModelProfile,
                                  batch_size: int) -> float:
        """Per-item share of a batched forward (decreasing in batch)."""
        return self.batched_inference_delay(variant, batch_size) / batch_size

    def sharded_inference_delay(self, variant: acc_mod.ModelProfile,
                                batch_size: int, n_devices: int = 1) -> float:
        """Cost of one batched forward sharded over a replica group.

        The batch splits evenly over the group's ``data`` axis, so the
        critical path is the largest per-device shard; ``n_devices == 1``
        reduces exactly to :meth:`batched_inference_delay`.
        """
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        per_device = -(-batch_size // n_devices)  # ceil division
        return self.batched_inference_delay(variant, per_device)

    def tick_inference_delay(self, group_costs) -> float:
        """Device-aware cost of one pod tick.

        ``group_costs``: per replica group, the summed delays of the
        dispatches it executed this tick.  Dispatches within a group
        serialise; groups run concurrently on disjoint devices, so the
        tick pays the MAX over groups — the single-device pod (one
        group) degenerates to the old sum-over-dispatches.
        """
        return max(group_costs, default=0.0)

    def tick_overlap_delay(self, group_costs: dict,
                           carry_in: dict | None = None) -> float:
        """:meth:`tick_inference_delay` generalised to overlapping
        dispatches (the event-clock runtime, ``repro.serving.runtime``).

        ``group_costs`` maps replica-group index to the summed delays
        of the dispatches the tick ADDED to that group; ``carry_in``
        maps group index to the busy seconds the group still owed past
        the tick start (work launched in an earlier tick under an
        async drain policy).  Each group completes at carry-in plus
        its serialised new work and the tick pays the max — with no
        carry-in this is exactly :meth:`tick_inference_delay`, which
        is what pins the sync policy's bit-identity.  ``PodServer``'s
        flush prices the carried tail through this closed form (with
        the event horizon as the floor for untouched busy groups).
        """
        carry = carry_in or {}
        return max((carry.get(g, 0.0) + c for g, c in group_costs.items()),
                   default=0.0)

    def variant_queue_cost(self, variant: acc_mod.ModelProfile,
                           n_requests: int, buckets=None,
                           n_devices: int = 1) -> float:
        """Device-busy seconds of draining ``n_requests`` of ``variant``.

        Exactly the variant's contribution to its replica group in one
        tick schedule: the requests split into bucket-capped chunks
        (``ShapeBuckets.split``) and each chunk is one sharded batched
        forward (:meth:`sharded_inference_delay`) — the same curve
        :meth:`tick_schedule_delay` prices, so the pod-level allocator
        and the tick model can never disagree on what a queue costs.
        Without ``buckets`` the whole count is one dispatch.
        """
        if n_requests <= 0:
            return 0.0
        chunks = buckets.split(n_requests) if buckets is not None \
            else [n_requests]
        return sum(self.sharded_inference_delay(variant, b, n_devices)
                   for b in chunks)

    def pod_amortization(self, variant: acc_mod.ModelProfile,
                         batch_size: int, buckets=None,
                         n_devices: int = 1) -> float:
        """Per-request share of the variant's tick drain, relative to
        the b=1 forward.

        ``== 1.0`` exactly at ``batch_size == 1`` on one device (the
        b=1 pin that keeps uncoupled plans byte-identical), decreasing
        as co-streams share the batch and as the replica group widens.
        The pod allocator scales each stream's base ``d_inf`` row by
        this factor, so coupling inherits whatever per-stream delivery
        estimates the base matrices carry.
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        total = self.variant_queue_cost(variant, batch_size, buckets,
                                        n_devices)
        return total / (batch_size * self.batched_inference_delay(variant, 1))

    def batched_pre_delay(self, variant: acc_mod.ModelProfile,
                          batch_size: int) -> float:
        """Cost of projecting/encoding ``batch_size`` PIs as one batch.

        The :meth:`_pre` stage follows the same sub-linear curve as the
        edge forward, with its own (shallower) ``pre_batch_marginal``;
        ``batch_size == 1`` reduces exactly to the per-request term.
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        return self._pre(variant) * (
            1.0 + (batch_size - 1) * self.pre_batch_marginal)

    def pre_amortization(self, variant: acc_mod.ModelProfile,
                         batch_size: int) -> float:
        """Per-request share of the batched mobile-side stage, relative
        to the b=1 projection/encode.

        ``== 1.0`` EXACTLY at ``batch_size == 1`` (the identity pin
        that keeps uncoupled d_pre pricing byte-identical), decreasing
        as co-streams share the mobile stage.  ``solve_pod``'s coupled
        price scales each stream's ``d_pre`` row by this factor.
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        pre = self._pre(variant)
        if pre <= 0.0:
            return 1.0
        return self.batched_pre_delay(variant, batch_size) / \
            (batch_size * pre)

    def tick_schedule_delay(self, schedule):
        """Price a whole tick's dispatch schedule on the pure curve.

        ``schedule``: one ``(variant, batch_size, n_devices,
        group_index)`` tuple per dispatch.  Returns ``(tick_delay,
        per-group sums)`` — the projection ``benchmarks/serving_bench``
        records, kept here so a future curve change cannot silently
        diverge from the serving path's pricing (``PodServer`` adds
        execution detail — marginal overrides, per-backend forwards —
        on top of these same methods).
        """
        group_sums: dict = {}
        for variant, batch_size, n_devices, gidx in schedule:
            group_sums[gidx] = group_sums.get(gidx, 0.0) + \
                self.sharded_inference_delay(variant, batch_size, n_devices)
        return self.tick_inference_delay(group_sums.values()), group_sums

    def observe_delivery(self, variant: acc_mod.ModelProfile) -> float:
        """Simulate one remote delivery, feed the passive profiler."""
        n_bytes = variant.input_size ** 2 * self.costs.bytes_per_pixel
        d = self.network.delivery_delay(n_bytes)
        self.profiler.observe(variant.name, d)
        return d


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------


def _in_sroi(det: sroi_mod.Detection, region: sroi_mod.SRoI) -> bool:
    ct, cp = region.center
    fh, fv = region.fov
    dlon = abs((det.box[0] - ct + math.pi) % (2 * math.pi) - math.pi)
    return dlon <= fh / 2 and abs(det.box[1] - cp) <= fv / 2


def _fully_enclosed(det: sroi_mod.Detection, region: sroi_mod.SRoI) -> bool:
    ct, cp = region.center
    fh, fv = region.fov
    dlon = abs((det.box[0] - ct + math.pi) % (2 * math.pi) - math.pi)
    return (dlon + det.box[2] / 2 <= fh / 2
            and abs(det.box[1] - cp) + det.box[3] / 2 <= fv / 2)


def _angular_distance(det: sroi_mod.Detection, region: sroi_mod.SRoI) -> float:
    ct, cp = region.center
    dlon = abs((det.box[0] - ct + math.pi) % (2 * math.pi) - math.pi)
    # great-circle distance (spherical law of cosines)
    cosd = (math.sin(cp) * math.sin(det.box[1])
            + math.cos(cp) * math.cos(det.box[1]) * math.cos(dlon))
    return math.acos(max(-1.0, min(1.0, cosd)))


@dataclasses.dataclass
class OracleBackend:
    """Ground-truth-driven detection sampling (see module docstring).

    ``semantic_batch``: the batched entry point is a pure simulation
    (no accelerator behind it), so the pod server prices a drained
    chunk spanning per-stream oracle instances as ONE shared-
    accelerator dispatch — the regime being simulated.
    """

    video: SyntheticVideo
    frame: int = 0
    seed: int = 0
    fp_rate: float = 0.02
    semantic_batch = True  # class-level: not a dataclass field

    def set_frame(self, frame: int) -> None:
        self.frame = frame

    def _detect(self, candidates, variant, region_tag: int,
                ref_sr: float = 4 * math.pi,
                region: sroi_mod.SRoI | None = None):
        out = []
        n_cat = self.video.n_categories
        fp_rng = np.random.default_rng(
            (self.seed * 1_000_003 + self.frame) * 131 + variant.index * 7
            + region_tag)
        for det in candidates:
            # temporally-coherent sampling: the hit decision for an
            # object re-randomises every few frames, not every frame —
            # real detectors find the same object in consecutive frames,
            # which is exactly what Algorithm 1's history exploits.
            okey = hash((round(float(det.box[2]), 6),
                         round(float(det.box[3]), 6), det.category))
            rng = np.random.default_rng(
                (self.seed * 7_368_787 + okey) % (2 ** 31)
                + variant.index * 97 + (self.frame // 4) * 31)
            # effective-resolution model: the object's share of THE
            # IMAGE IT IS ANALYSED IN decides its gav size level
            level = sroi_mod.size_level_in(det, ref_sr, acc_mod.SMALL_NOA,
                                           acc_mod.MEDIUM_NOA)
            acc = float(variant.gav[level * n_cat + det.category % n_cat])
            if region is not None:
                # geometric penalties of analysing a PI (paper Fig. 1):
                # (a) objects cut by the PI border are detected poorly —
                #     CubeMap's fixed 90-degree grid splits constantly,
                #     SRoIs are centred on objects by construction;
                # (b) gnomonic stretch away from the tangent point
                #     degrades off-axis objects (1 at centre, ~cos^2 d).
                if not _fully_enclosed(det, region):
                    acc *= 0.3
                d = _angular_distance(det, region)
                acc *= max(math.cos(min(d, math.pi / 2)), 0.15) ** 2
            if rng.uniform() < acc:
                jitter = (1.0 - acc) * 0.1
                box = det.box.copy()
                box[0] += rng.normal(0, jitter * box[2])
                box[1] += rng.normal(0, jitter * box[3])
                box[2] *= float(np.exp(rng.normal(0, jitter)))
                box[3] *= float(np.exp(rng.normal(0, jitter)))
                out.append(sroi_mod.Detection(
                    box=box, category=det.category,
                    score=float(np.clip(acc + rng.normal(0, 0.05), 0.05, 1.0))))
        if fp_rng.uniform() < self.fp_rate and candidates:
            ref = candidates[0]
            out.append(sroi_mod.Detection(
                box=ref.box * np.array([1.0, 1.0, 0.7, 0.7]),
                category=int(fp_rng.integers(0, n_cat)), score=0.3))
        return out

    def infer_sroi(self, frame_img, region: sroi_mod.SRoI,
                   variant: acc_mod.ModelProfile):
        del frame_img
        gt = self.video.visible_objects(self.frame)
        cands = [d for d in gt if _in_sroi(d, region)]
        tag = hash((round(region.center[0], 3), round(region.center[1], 3))) % 9973
        return self._detect(cands, variant, tag,
                            ref_sr=sroi_mod.region_solid_angle(*region.fov),
                            region=region)

    def infer_srois_batched(self, items, variant: acc_mod.ModelProfile):
        """Batched entry point of the variant-queue machinery.

        ``items`` is a list of ``(frame_img, region)`` pairs.  The
        oracle samples from per-stream ground truth, so the "batch" is
        semantic — results are bit-identical to per-request
        :meth:`infer_sroi` calls, which is exactly what the
        batched-vs-inline equivalence tests pin.
        """
        return [self.infer_sroi(frame_img, region, variant)
                for frame_img, region in items]

    def infer_erp(self, frame_img, variant: acc_mod.ModelProfile):
        """Full-ERP inference: distortion + downsampling degrade small
        objects — modelled as a size-level demotion of the gav."""
        del frame_img
        gt = self.video.visible_objects(self.frame)
        demoted = dataclasses.replace(
            variant, gav=np.concatenate([
                variant.gav[:len(variant.gav) // 3] * 0.3,   # small: mostly lost
                variant.gav[len(variant.gav) // 3: 2 * len(variant.gav) // 3] * 0.6,
                variant.gav[2 * len(variant.gav) // 3:] * 0.9,
            ]))
        return self._detect(gt, demoted, region_tag=0, ref_sr=4 * math.pi)


def _pad_last(rows: list, n: int) -> list:
    """``rows`` padded to ``n`` by repeating the last: a padding row of
    a batched dispatch is a copy of the chunk's last real row."""
    return rows + [rows[-1]] * (n - len(rows))


class JaxDetectorBackend:
    """Real path: Pallas gnomonic projection + JAX detector inference.

    Exposes BOTH execution paths of the serving loop:

      * :meth:`infer_sroi` — the per-request path (one PI through the
        smallest batch rung's jitted program), used by standalone loops
        and as the batching baseline;
      * :meth:`infer_srois_batched` — the pod path: the tick's crops
        for one variant are stacked, zero-padded up to a batch-size
        bucket (``repro.serving.batching.ShapeBuckets``) and pushed
        through ONE jitted ``apply`` + masked ``decode``.  The jit
        cache is keyed by (variant, padded batch), so a serving
        lifetime compiles at most ``len(buckets) * n_variants``
        distinct programs no matter how stream counts fluctuate
        (``trace_count`` counts actual retraces for the regression
        tests).  Each program is named for its shape bucket,
        ``forward_<variant>_b<padded batch>``.

    Both paths back-project a chunk's decoded PI boxes to SphBBs with
    ONE jitted program, ``backproject_s<size>_b<padded batch>``, queued
    on the device behind the forward, and bring the chunk's scores,
    classes and SphBBs back in ONE pull.

    ``telemetry`` (the owning ``PodServer`` hands down its sink; a
    no-op by default) times the steps of a batched dispatch as spans,
    ``drain.stage`` (cache lookups, ERP upload and stack),
    ``drain.project``, ``drain.forward``, and per chunk
    ``drain.backproject`` (the launch) and ``drain.fetch`` (the pull);
    the full-ERP pass is ``drain.discovery``.  It counts
    ``upload_bytes`` (every host frame turned into a device array),
    ``staged_rows`` (crops whose ERP was uploaded for projection,
    padding rows included) and ``backproject_rows`` (rows through the
    back-projection program, padding rows included).
    """

    def __init__(self, variants_cfg, params_per_variant, conf: float = 0.25,
                 use_kernel: bool = True, max_det: int = 16, buckets=None,
                 fused: bool = True, crop_cache_size: int = 256):
        from repro.serving.batching import ShapeBuckets

        self.cfgs = list(variants_cfg)
        self.params = list(params_per_variant)
        self.conf = conf
        self.use_kernel = use_kernel
        self.max_det = max_det
        self.buckets = buckets or ShapeBuckets(
            resolutions=tuple(sorted({c.input_size for c in self.cfgs})))
        self._jit_cache: dict = {}
        # (variant, group device ids) -> params replicated on that
        # replica group's mesh, placed once instead of per dispatch
        self._group_params: dict = {}
        self.trace_count = 0  # incremented at trace time only
        # (pi_box_to_sphbb, padded batch, PI size) -> the jitted
        # back-projection program, and its own trace counter
        self._backproject_cache: dict = {}
        self.backproject_trace_count = 0
        # fused tick: batched gnomonic projection (one dispatch per
        # chunk instead of one `_project` per crop) + a cross-tick crop
        # cache keyed on pitch-quantised region geometry.  `fused=False`
        # restores the staged per-crop path (the bench baseline).
        self.fused = fused
        self.crop_cache_size = crop_cache_size if fused else 0
        self._crop_cache: dict = {}  # key -> (guard, pi, ct, cp, fx, fy)
        self.crop_cache_hits = 0
        self.crop_cache_misses = 0
        self.telemetry = TelemetrySink()

    def _upload(self, frame_img):
        """``frame_img`` as a device array, its bytes counted when it
        came from the host.  A host frame goes up flat and one reshape
        program lays it out on the device.  The device keeps an
        (H, W, 3) float32 frame channel-planar and tiled, so uploading
        it as it is makes the host relay it out first, in tens of
        thousands of small transposes a frame, each an event in a
        profiler's host trace: enough to run a profiled window out of
        host memory.  Flat, the host copies it as it is, and the device
        pays one relayout a frame instead."""
        import jax.numpy as jnp

        if not isinstance(frame_img, np.ndarray):
            return jnp.asarray(frame_img)
        self.telemetry.count("upload_bytes", frame_img.nbytes)
        flat = np.ascontiguousarray(frame_img).reshape(-1)
        return jnp.asarray(flat).reshape(frame_img.shape)

    def _project(self, frame_img, region: sroi_mod.SRoI, size: int):
        """SRoI -> (size, size, 3) PI; shared by both execution paths
        so batched and per-request crops are identical."""
        import jax.numpy as jnp

        self.telemetry.count("staged_rows", 1)
        erp = self._upload(frame_img)
        if self.use_kernel:
            from repro.kernels.gnomonic import ops as gno_ops

            return gno_ops.project_sroi_kernel(
                erp, region.center[0], region.center[1],
                region.fov, (size, size))
        from repro.core.projection import project_sroi

        return project_sroi(erp,
                            jnp.asarray(region.center[0]),
                            jnp.asarray(region.center[1]),
                            region.fov, (size, size))

    def _backproject_fn(self, b_pad: int, size: int):
        """The jitted back-projection program for one (padded batch,
        PI size): ``(b_pad, max_det, 4)`` PI boxes and a ``(b_pad, 4)``
        per-row geometry ``(ct, cp, fov_x, fov_y)`` to ``(b_pad,
        max_det, 4)`` SphBBs, ``pi_box_to_sphbb`` broadcasting each
        row's geometry over its detections.  Named
        ``backproject_s<size>_b<b_pad>``.

        ``pi_box_to_sphbb`` is looked up when the program is asked for
        and is part of the cache key, so a replaced implementation is
        the one that runs.  The cache and ``backproject_trace_count``
        are the back-projection's own: ``_jit_cache`` and
        ``trace_count`` count the forwards alone."""
        import jax

        impl = pi_box_to_sphbb
        key = (impl, b_pad, size)
        fn = self._backproject_cache.get(key)
        if fn is None:
            def traced(boxes, geom):
                self.backproject_trace_count += 1  # trace time only
                g = geom[:, :, None]  # (b_pad, 4, 1): broadcast per row
                return impl(boxes, g[:, 0], g[:, 1], (g[:, 2], g[:, 3]),
                            (size, size))

            traced.__name__ = traced.__qualname__ = (
                f"backproject_s{size}_b{b_pad}")
            fn = self._backproject_cache[key] = jax.jit(traced)
        return fn

    def _launch_backproject(self, boxes, chunk, geoms, size: int):
        """Queue one chunk's back-projection behind its forward, on the
        forward's device ``boxes`` as they are.  Each row lifts through
        ``geoms[r]`` (a cache hit's anchor geometry) or, where that is
        None, its own region; padding rows repeat the last real row.
        The geometry goes as an uncommitted host array, so it follows
        ``boxes`` to whatever devices they are sharded over."""
        b_pad = boxes.shape[0]
        rows = [g if g is not None else (region.center[0], region.center[1],
                                         region.fov)
                for g, (_, region) in zip(geoms, chunk)]
        geom = np.array([(ct, cp, fov[0], fov[1])
                         for ct, cp, fov in _pad_last(rows, b_pad)],
                        np.float32)
        with self.telemetry.span("drain.backproject", b=len(chunk),
                                 padded=b_pad):
            sphbbs = self._backproject_fn(b_pad, size)(boxes, geom)
        self.telemetry.count("backproject_rows", b_pad)
        return sphbbs

    def _fetch_dets(self, n: int, scores, classes, sphbbs) -> list[list]:
        """ONE device->host pull of a chunk's scores, classes and
        SphBBs, then its first ``n`` rows' detections on the host: each
        row's live (``score > 0``) entries in decode order.  Padding
        rows and zero-score entries were back-projected with the rest
        of the chunk and are dropped here."""
        import jax

        with self.telemetry.span("drain.fetch", b=n):
            scores, classes, sphbbs = jax.device_get(
                (scores, classes, sphbbs))
        return [[sroi_mod.Detection(box=sphbbs[r, k],
                                    category=int(classes[r, k]),
                                    score=float(scores[r, k]))
                 for k in np.flatnonzero(scores[r] > 0)]
                for r in range(n)]

    def _forward_one(self, idx: int, img):
        """One (S, S, 3) image through the smallest batch rung's jitted
        program (masked padding rows), so the per-request and discovery
        paths share the batched path's compiled forwards.  Returns the
        padded ``(boxes, scores, classes)``; row 0 is the image's."""
        import jax.numpy as jnp

        b_pad = self.buckets.pad_batch(1)
        imgs = img[None]
        if b_pad > 1:
            imgs = jnp.concatenate(
                [imgs, jnp.zeros((b_pad - 1,) + img.shape, img.dtype)])
        boxes, scores, classes, _ = self._batched_fn(idx, b_pad)(
            self.params[idx], imgs, jnp.arange(b_pad) < 1)
        return boxes, scores, classes

    def infer_sroi(self, frame_img, region: sroi_mod.SRoI,
                   variant: acc_mod.ModelProfile):
        idx = variant.index - 1
        size = self.cfgs[idx].input_size
        pi = self._project(frame_img, region, size)
        boxes, scores, classes = self._forward_one(idx, pi)
        item = [(frame_img, region)]
        sphbbs = self._launch_backproject(boxes, item, [None], size)
        return self._fetch_dets(1, scores, classes, sphbbs)[0]

    def _batched_fn(self, idx: int, b_pad: int, group=None):
        """The jitted (apply + masked decode) program for one
        (variant, padded-batch) shape bucket — ``shard_map``-sharded
        over ``group``'s ``data`` mesh axis when a multi-device replica
        group is given (the multi-device serving path).

        Returns ``(boxes, scores, classes, heads)``: the decoded rows and
        the raw per-scale head outputs they were decoded from, so the
        served program itself can be checked against a reference
        forward (decode's argmax and top-k are not continuous in the
        heads; the heads are)."""
        import jax

        key = (idx, b_pad) if group is None or group.n_devices == 1 else (
            idx, b_pad, tuple(getattr(d, "id", d) for d in group.devices))
        fn = self._jit_cache.get(key)
        if fn is None:
            from repro.models import detector as det_mod

            cfg = self.cfgs[idx]

            def forward(params, imgs, valid):
                outs = det_mod.apply(params, imgs, cfg)
                return (*det_mod.decode(outs, cfg, self.conf,
                                        max_det=self.max_det, valid=valid),
                        outs)

            if len(key) == 3:
                from jax.sharding import PartitionSpec as P

                from repro.distributed.sharding import (
                    no_activation_constraints)

                inner = forward

                def forward(params, imgs, valid):  # noqa: F811
                    # rows are independent, so per-device shards decode
                    # exactly like the unsharded batch; the training-
                    # oriented activation constraints are meaningless
                    # inside the manual (per-device) region.
                    with no_activation_constraints():
                        return jax.shard_map(
                            inner, mesh=group.mesh,
                            in_specs=(P(), P("data"), P("data")),
                            out_specs=P("data"),
                            check_vma=False)(params, imgs, valid)

            def traced(params, imgs, valid):
                self.trace_count += 1  # runs at trace time only
                return forward(params, imgs, valid)

            # the program's name in traces and compile logs
            traced.__name__ = traced.__qualname__ = (
                f"forward_{cfg.name}_b{b_pad}" + ("" if len(key) == 2 else
                "_d" + "-".join(map(str, key[2]))))
            fn = self._jit_cache[key] = jax.jit(traced)
        return fn

    def _params_for(self, idx: int, group=None):
        """Variant ``idx``'s params where its forward runs: replicated
        over a multi-device group's mesh (placed on first use), else
        the arrays as given."""
        if group is None or group.n_devices == 1:
            return self.params[idx]
        key = (idx, tuple(d.id for d in group.devices))
        placed = self._group_params.get(key)
        if placed is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            placed = self._group_params[key] = jax.device_put(
                self.params[idx], NamedSharding(group.mesh, P()))
        return placed

    # ---- cross-tick crop cache -------------------------------------
    #
    # Static scenes re-project near-identical SRoIs tick after tick.
    # A crop is reusable when (a) the source frame is the same array
    # (identity + a strided content guard, so id() reuse after gc can
    # never alias a different frame) and (b) the region geometry moved
    # less than the bucket's pixel pitch (fov / size): quantising
    # centre and fov at the pitch makes sub-pixel drift hash to the
    # anchor's key.  Hits return the anchor's PI *and geometry*, so
    # back-projection is bit-identical to re-serving the anchor region.

    @staticmethod
    def _frame_guard(frame_img) -> bytes:
        h, w = frame_img.shape[:2]
        sample = np.asarray(frame_img[::max(1, h // 8), ::max(1, w // 8)])
        return np.ascontiguousarray(sample).tobytes()

    @staticmethod
    def _crop_key(frame_img, region: sroi_mod.SRoI, size: int):
        fx, fy = float(region.fov[0]), float(region.fov[1])
        px, py = fx / size, fy / size  # radians per output pixel
        return (id(frame_img), frame_img.shape[:2], size,
                round(float(region.center[0]) / px),
                round(float(region.center[1]) / py),
                round(fx / px), round(fy / py))

    def _cache_put(self, key, guard, pi, region: sroi_mod.SRoI) -> None:
        if len(self._crop_cache) >= self.crop_cache_size:
            self._crop_cache.pop(next(iter(self._crop_cache)))
        self._crop_cache[key] = (
            guard, pi, float(region.center[0]), float(region.center[1]),
            (float(region.fov[0]), float(region.fov[1])))

    def _project_chunk(self, chunk, size: int):
        """Project one chunk's crops: cache lookups + ONE batched
        gnomonic dispatch for the misses (padded to a batch rung so the
        projector compiles once per (bucket, ERP shape, size)).

        Returns ``(pis, geoms)`` — the (b, S, S, 3) PI stack and the
        per-item back-projection geometry (the anchor's for hits).
        """
        import jax.numpy as jnp

        from repro.kernels.gnomonic.ops import project_srois_batched

        tel = self.telemetry
        b = len(chunk)
        rows: list = [None] * b
        geoms: list = [None] * b
        miss: list[int] = []
        guards: dict[int, bytes] = {}  # per distinct frame per chunk
        keys: list = [None] * b
        with tel.span("drain.stage", b=b):
            for i, (frame_img, region) in enumerate(chunk):
                geoms[i] = (region.center[0], region.center[1],
                            (float(region.fov[0]), float(region.fov[1])))
                if not self.crop_cache_size:
                    miss.append(i)
                    continue
                key = keys[i] = self._crop_key(frame_img, region, size)
                ent = self._crop_cache.get(key)
                if ent is not None:
                    guard = guards.get(id(frame_img))
                    if guard is None:
                        guard = guards[id(frame_img)] = self._frame_guard(
                            frame_img)
                    if ent[0] == guard:
                        self.crop_cache_hits += 1
                        rows[i] = ent[1]
                        geoms[i] = (ent[2], ent[3], ent[4])
                        continue
                self.crop_cache_misses += 1
                miss.append(i)
            b_proj = self.buckets.pad_batch(len(miss)) if miss else 0
            if miss:
                sel = _pad_last(miss, b_proj)
                # one upload per projected row: padding rows repeat the
                # last crop's frame
                erps = jnp.stack([self._upload(chunk[i][0]) for i in sel])
                tel.count("staged_rows", b_proj)
        with tel.span("drain.project", b=len(miss), padded=b_proj):
            if miss:
                fresh = project_srois_batched(
                    erps, [chunk[i][1].center for i in sel],
                    [chunk[i][1].fov for i in sel], (size, size))
                for j, i in enumerate(miss):
                    rows[i] = fresh[j]
                    if self.crop_cache_size:
                        guard = guards.get(id(chunk[i][0]))
                        if guard is None:
                            guard = guards[id(chunk[i][0])] = \
                                self._frame_guard(chunk[i][0])
                        self._cache_put(keys[i], guard, fresh[j],
                                        chunk[i][1])
            return jnp.stack(rows), geoms

    def launch_srois_batched(self, items, variant: acc_mod.ModelProfile,
                             group=None):
        """Launch the padded batched forward(s) for a tick's
        same-variant crops WITHOUT blocking on the result.

        Returns a zero-argument resolver producing the per-item
        detection lists.  Jax dispatch is asynchronous, so a caller
        that launches every replica group's forward before resolving
        any of them overlaps the V variants' inference across their
        disjoint device groups — the multi-device tick.

        With ``fused=True`` (default) the chunk's crops project in ONE
        batched gnomonic dispatch (cache hits skip projection entirely)
        instead of one ``_project`` per crop; ``fused=False`` keeps the
        staged per-crop path as the measured baseline.

        Each chunk's back-projection is launched right behind its
        forward, so the resolver does one jitted program's worth of
        waiting and ONE device->host pull per chunk, then builds the
        rows' detections from NumPy slices.
        """
        import jax.numpy as jnp

        idx = variant.index - 1
        cfg = self.cfgs[idx]
        size = self.buckets.bucket_resolution(cfg.input_size)
        tel = self.telemetry
        launched = []  # (real rows, scores, classes, sphbbs)
        lo = 0
        for b in self.buckets.split(len(items)):
            chunk = items[lo:lo + b]
            lo += b
            if self.fused:
                pis, geoms = self._project_chunk(chunk, size)
            else:
                with tel.span("drain.project", variant=idx, b=b, padded=b):
                    pis = jnp.stack([self._project(f, r, size)
                                     for f, r in chunk])
                geoms = [None] * b
            b_pad = self.buckets.pad_batch(b)
            if group is not None and group.n_devices > 1:
                # pad further to a group-width multiple so the batch
                # axis shards evenly over the group's `data` axis
                b_pad = group.shard_batch(b_pad)
            with tel.span("drain.forward", variant=idx, b=b, padded=b_pad):
                if b_pad > b:
                    pis = jnp.concatenate(
                        [pis, jnp.zeros((b_pad - b,) + pis.shape[1:],
                                        pis.dtype)])
                valid = jnp.arange(b_pad) < b
                boxes, scores, classes, _ = self._batched_fn(
                    idx, b_pad, group)(self._params_for(idx, group), pis,
                                       valid)
            sphbbs = self._launch_backproject(boxes, chunk, geoms, size)
            launched.append((b, scores, classes, sphbbs))

        def resolve() -> list[list]:
            out: list[list] = []
            for n, scores, classes, sphbbs in launched:
                out.extend(self._fetch_dets(n, scores, classes, sphbbs))
            return out

        return resolve

    def infer_srois_batched(self, items, variant: acc_mod.ModelProfile,
                            group=None):
        """ONE padded batched forward for a tick's same-variant crops.

        ``items``: list of ``(frame_img, region)``.  Crops are
        projected at the variant's (bucketed) input resolution, stacked
        into (B, S, S, 3), zero-padded up to the batch bucket and run
        through the jitted forward with a validity mask; decoded rows
        back-project to SphBBs exactly like the per-request path.
        Chunks larger than the top bucket split into bucket-sized
        dispatches.  With a multi-device ``group`` the batch axis
        shards over the group's mesh (see :meth:`launch_srois_batched`,
        the non-blocking form the pod drain uses).
        """
        return self.launch_srois_batched(items, variant, group)()

    def infer_erp(self, frame_img, variant: acc_mod.ModelProfile):
        # ERP-wide pass with the largest model on the resized frame
        import jax
        import jax.numpy as jnp

        from repro.core.projection import resize_erp

        idx = variant.index - 1
        size = self.cfgs[idx].input_size
        with self.telemetry.span("drain.discovery", variant=idx):
            resized = resize_erp(self._upload(frame_img), (size, size))
            boxes, scores, classes = jax.device_get(
                self._forward_one(idx, resized))
            h, w = frame_img.shape[:2]
            dets = []
            for b, s, c in zip(boxes[0], scores[0], classes[0]):
                if s <= 0:
                    continue
                # rectangular BB on the ERP -> SphBB via ERP coords
                x0, y0, x1, y1 = b * np.array([w / size, h / size] * 2)
                theta = ((x0 + x1) / 2 / w - 0.5) * 2 * math.pi
                phi = (0.5 - (y0 + y1) / 2 / h) * math.pi
                dth = (x1 - x0) / w * 2 * math.pi
                dph = (y1 - y0) / h * math.pi
                dets.append(sroi_mod.Detection(
                    box=np.array([theta, phi, abs(dth), abs(dph)]),
                    category=int(c), score=float(s)))
            return dets
