"""Decide ``correct``: what the timed window served against the reference.

Each number compared is the worst over a seeded sample of what the
window itself served, at the served sizes:

  ``proj_err``     mean |served crop - reference crop| of the worst
                   sampled well-posed crop (:func:`well_posed_crop`;
                   pixel values in [0, 1]); the reference projects the
                   same frame at the geometry the crop was served with;
  ``heads_err``    ||served heads - reference heads|| / ||reference
                   heads|| of the worst row: the served batched forward
                   against the reference forward at HIGHEST on the same
                   served crop (the forward is chaotic in its input: a
                   crop that moved by rounding would move the heads as
                   much as a fault);
  ``decode_err``   each served detection against the nearest anchor of
                   the same class decoded by the reference from the same
                   served heads (box error over the crop size, score
                   error), and the served top scores against the
                   reference's, worst of the three;
  ``backproj_err`` served SphBB against the reference back-projection of
                   the served pixel box (radians, longitude wrapped),
                   worst well-posed box (:func:`well_posed_boxes`);
  ``nms_flips``    sampled frames whose served keep-mask differs from
                   the reference greedy NMS on the served boxes, leaving
                   out frames that hold a pair within ``IOU_EDGE`` of the
                   threshold (float32 SphIoU may round either way there).

Matrix products (the convolutions, the geometry's rotations) are
referenced at the precision the configuration states, one bfloat16 pass:
operands rounded to bfloat16, products summed in float32 (HIGHEST) or
float64.  Everything else is float64 (NumPy) or float32.  The control
puts the reference, one precision lower, in the program's place for every
number (:func:`control_numbers`): it must fail.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference as ref

IOU_EDGE = 1e-4
FOV_LIMIT = 2.0  # rad


def _dtypes(config: dict):
    """(stated, below): the operand type the configuration states for
    its matrix products (``matmul_operands``: "bfloat16" for one bfloat16
    pass, products summed in float32, the TPU's default; "float32" for
    exact float32 products, as on the CPU), None meaning no rounding, and
    the type one below it (float8 e4m3, or bfloat16)."""
    import ml_dtypes

    if config["matmul_operands"] == "bfloat16":
        return ml_dtypes.bfloat16, ml_dtypes.float8_e4m3fn
    if config["matmul_operands"] == "float32":
        return None, ml_dtypes.bfloat16
    raise ValueError(f"matmul_operands {config['matmul_operands']!r}")
NMS_SAMPLE = 64
NAMES = ("proj_err", "heads_err", "decode_err", "backproj_err", "nms_flips")


def _heads_error(got, want) -> float:
    """||got - want|| / ||want|| over every head of one row (RMS, not the
    largest element: the random-weight network turns one rounding into
    outliers that a fault and a rounding share alike)."""
    d = sum(float(np.sum((np.asarray(g, np.float64) - w) ** 2))
            for g, w in zip(got, want))
    n = sum(float(np.sum(np.asarray(w, np.float64) ** 2)) for w in want)
    return math.sqrt(d / max(n, 1e-300))


def well_posed_crop(geom) -> bool:
    """A crop whose gnomonic projection is well conditioned: both fields
    of view under ``FOV_LIMIT``.  Near 180 degrees ``tan(fov / 2)`` runs
    off, and float32 and float64 place the same pixel far apart; random
    weights make such regions (from huge boxes) common."""
    return max(geom[2]) < FOV_LIMIT


def well_posed_boxes(boxes, size):
    """Boxes whose corners lie within one crop size of the crop: farther
    out, lifting a corner to the sphere nears the tangent plane's
    horizon and float rounding moves its longitude across the seam."""
    return np.all((boxes >= -size) & (boxes <= 2 * size), axis=-1)


def _decode_error(heads, strides, size, boxes, scores, classes, conf,
                  max_det) -> float:
    cb, cs, cc = ref.decode_candidates(heads, strides)
    live = np.flatnonzero(scores > 0)
    # as many detections as anchors over the confidence floor, up to
    # max_det (an anchor within 1e-6 of the floor may go either way)
    lo = min(max_det, int(np.sum(cs >= conf + 1e-6)))
    hi = min(max_det, int(np.sum(cs >= conf - 1e-6)))
    if not lo <= len(live) <= hi:
        return math.inf
    worst = 0.0
    for j in live:
        same = cc == classes[j]
        if not same.any():
            return math.inf
        d = np.maximum(np.abs(cb[same] - boxes[j]).max(-1) / size,
                       np.abs(cs[same] - scores[j]))
        worst = max(worst, float(d.min()))
    k = len(live)
    if k:
        top = np.sort(cs)[::-1][:k]
        worst = max(worst, float(np.abs(np.sort(scores[live])[::-1]
                                        - top).max()))
    return worst


def _wrap(a):
    return (a + math.pi) % (2 * math.pi) - math.pi


def _backproj_error(served_dets, boxes, scores, geom, size,
                    dot_dt) -> float:
    """Largest SphBB component error over the row's well-posed
    detections (:func:`well_posed_boxes`, of a well-posed crop) against
    the float64 back-projection of the same pixel boxes (rad)."""
    live = np.flatnonzero(scores > 0)
    if len(live) != len(served_dets):
        return math.inf
    keep = well_posed_boxes(boxes[live].astype(np.float64), size)
    if not len(live) or not keep.any() or not well_posed_crop(geom):
        return 0.0
    want = ref.backproject(boxes[live][keep].astype(np.float64),
                           (geom[0], geom[1]), geom[2], size,
                           dot_dt=dot_dt)
    got = np.stack([d[0] for d in served_dets])[keep]
    diff = np.abs(got - want)
    diff[:, 0] = np.abs(_wrap(got[:, 0] - want[:, 0]))
    return float(diff.max())


def _nms_flips(frames, threshold: float,
               served_keep=None) -> tuple[int, int]:
    """(frames that differ, frames left out as on the edge)."""
    flips = edge = 0
    for i, (boxes, scores, keep) in enumerate(frames):
        want, iou = ref.nms(boxes, scores, threshold)
        got = keep if served_keep is None else served_keep[i]
        if np.array_equal(got, want):
            continue
        off = iou[np.triu_indices(len(scores), 1)]
        if np.any(np.abs(off - threshold) < IOU_EDGE):
            edge += 1
        else:
            flips += 1
    return flips, edge


def nms_sample(nms: list, seed: int) -> list:
    rng = np.random.default_rng((seed, 0x4E))
    if len(nms) <= NMS_SAMPLE:
        return list(nms)
    # the frames with the most detections, and a draw from the rest
    order = sorted(range(len(nms)), key=lambda i: -len(nms[i][1]))
    pick = order[:NMS_SAMPLE // 4]
    rest = order[NMS_SAMPLE // 4:]
    pick += list(rng.choice(rest, NMS_SAMPLE - len(pick), replace=False))
    return [nms[i] for i in sorted(pick)]


def reference_crops(rec, dot_dt, dt=np.float64, rot_dt="same"):
    """The reference's crops for one served dispatch's real rows (rows
    in threads: NumPy releases the interpreter lock)."""
    import concurrent.futures

    def one(job):
        (frame, _, _), g = job
        return ref.project(frame, (g[0], g[1]), g[2], rec.size, dt, dot_dt,
                           rot_dt)

    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        return np.stack(list(ex.map(one, zip(rec.items, rec.geoms))))


def served_numbers(served: list, nms: list, config: dict, seed: int,
                   params_ref: list, log=None) -> dict:
    """Every number of :data:`NAMES` for what a window served.
    ``params_ref``: the reference's own weights per rung."""
    dets_cfg = config["detectors"]
    stated, _ = _dtypes(config)
    fwd = _ref_forward(params_ref, dets_cfg, stated)
    out = {n: 0.0 for n in NAMES}
    rows = 0
    for rec in served:
        st = ref.strides(dets_cfg[rec.variant])
        boxes, scores, classes = (np.asarray(a) for a in rec.out[:3])
        heads = [np.asarray(h) for h in rec.out[3]]
        pis = np.asarray(rec.pis, np.float64)[:rec.b]
        proj = np.abs(pis - reference_crops(rec, stated)).mean(axis=(1, 2, 3))
        posed = [well_posed_crop(g) for g in rec.geoms]
        out["proj_err"] = max([out["proj_err"]] + [
            float(e) for e, ok in zip(proj, posed) if ok])
        want = fwd(rec.variant, pis)
        for r in range(rec.b):
            rows += 1
            h = _heads_error([x[r] for x in heads], [w[r] for w in want])
            d = _decode_error([x[r] for x in heads], st, rec.size, boxes[r],
                              scores[r], classes[r], config["conf"],
                              config["max_det"])
            bp = _backproj_error(rec.dets[r], boxes[r], scores[r],
                                 rec.geoms[r], rec.size, stated)
            out["heads_err"] = max(out["heads_err"], h)
            out["decode_err"] = max(out["decode_err"], d)
            out["backproj_err"] = max(out["backproj_err"], bp)
            if log is not None:
                log(f"row {dets_cfg[rec.variant]['name']} b={rec.b} r={r} "
                    f"fov {max(rec.geoms[r][2]):.3f}: proj {proj[r]:.3e} "
                    f"heads {h:.3e} decode {d:.3e} backproj {bp:.3e}")
    flips, edge = _nms_flips(nms_sample(nms, seed), config["nms_threshold"])
    out["nms_flips"] = float(flips)
    out["_rows"] = rows
    out["_nms_frames"] = min(len(nms), NMS_SAMPLE)
    out["_nms_edge"] = edge
    return out


def _ref_forward(params_ref, dets_cfg, operand_dtype=None):
    """``run(rung, crops)``: the reference forward of each crop alone
    (one compiled program per rung), heads as float64 arrays."""
    import jax
    import jax.numpy as jnp

    def run(idx, crops):
        fn = ref.forward_fn(dets_cfg[idx], operand_dtype)
        outs = [fn(params_ref[idx], jnp.asarray(crops[r:r + 1], jnp.float32))
                for r in range(len(crops))]
        return [np.concatenate([np.asarray(jax.device_get(o[s]), np.float64)
                                for o in outs])
                for s in range(len(outs[0]))]

    return run


def control_numbers(served: list, nms: list, config: dict, seed: int,
                    params_ref: list) -> dict:
    """The numbers of :data:`NAMES` when the reference one precision
    below what the configuration states stands in for the program, on
    the same served inputs: bfloat16 arithmetic for projection, decode,
    back-projection and SphIoU, and matrix-product operands one type
    below the stated one.  Each is compared as the served output is."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    stated, below = _dtypes(config)
    dets_cfg = config["detectors"]
    fwd_low = _ref_forward(params_ref, dets_cfg, below)
    fwd = _ref_forward(params_ref, dets_cfg, stated)
    out = {n: 0.0 for n in NAMES}
    for rec in served:
        st = ref.strides(dets_cfg[rec.variant])
        pis = np.asarray(rec.pis, np.float64)[:rec.b]
        low = reference_crops(rec, below, bf).astype(np.float64)
        proj = np.abs(low - reference_crops(rec, stated)).mean(axis=(1, 2, 3))
        out["proj_err"] = max([out["proj_err"]] + [
            float(e) for e, g in zip(proj, rec.geoms)
            if well_posed_crop(g)])
        want = fwd(rec.variant, pis)
        got = fwd_low(rec.variant, pis)
        for r in range(rec.b):
            heads = [w[r] for w in want]
            out["heads_err"] = max(out["heads_err"], _heads_error(
                [g[r] for g in got], heads))
            cb, cs, cc = ref.decode_candidates(heads, st, bf)
            top = np.argsort(-cs.astype(np.float64), kind="stable")[
                :config["max_det"]]
            boxes = cb[top].astype(np.float64)
            scores = np.where(cs[top] >= config["conf"], cs[top], 0
                              ).astype(np.float64)
            out["decode_err"] = max(out["decode_err"], _decode_error(
                heads, st, rec.size, boxes, scores, cc[top], config["conf"],
                config["max_det"]))
            live = np.flatnonzero(scores > 0)
            g = rec.geoms[r]
            low_bp = ref.backproject(boxes[live], (g[0], g[1]), g[2],
                                     rec.size, bf, below).astype(np.float64)
            out["backproj_err"] = max(out["backproj_err"], _backproj_error(
                [(b,) for b in low_bp], boxes, scores, g, rec.size, stated))
    frames = nms_sample(nms, seed)
    keeps = [ref.nms(b, s, config["nms_threshold"], bf)[0]
             for b, s, _ in frames]
    flips, _ = _nms_flips(frames, config["nms_threshold"], served_keep=keeps)
    out["nms_flips"] = float(flips)
    return out


def diagnose(served: list, config: dict, params_ref: list, log) -> None:
    """Per sampled row, the errors against the other candidate
    references: crops against float64 with exact rotations and with
    only the second product rounded, heads against float32 at HIGHEST
    (what chose the stated precision; see PERF.md)."""
    dets_cfg = config["detectors"]
    stated, _ = _dtypes(config)
    f32 = _ref_forward(params_ref, dets_cfg)
    for rec in served:
        pis = np.asarray(rec.pis, np.float64)[:rec.b]
        exact = np.abs(pis - reference_crops(rec, None)).mean(axis=(1, 2, 3))
        second = np.abs(pis - reference_crops(rec, stated, rot_dt=None)
                        ).mean(axis=(1, 2, 3))
        heads = [np.asarray(h) for h in rec.out[3]]
        want = f32(rec.variant, pis)
        for r in range(rec.b):
            h = _heads_error([x[r] for x in heads], [w[r] for w in want])
            log(f"diag {dets_cfg[rec.variant]['name']} r={r} fov "
                f"{max(rec.geoms[r][2]):.3f}: proj exact {exact[r]:.3e} "
                f"second-only {second[r]:.3e} heads f32 {h:.3e}")


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[n] <= limits[n] for n in NAMES)
