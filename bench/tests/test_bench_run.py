"""A whole run on the CPU at a small size: the result line, the control,
and faults planted under the served path, each of which must come out
as not correct.

The harness's look for a chip (``run.main``) is skipped: the test drives
``run.Session`` directly, at 64/96 px crops of 64x128 ERP frames with
two streams, and plants each fault in the program's own functions, under
the wrappers that record what was served.
"""

import jax
import numpy as np
import pytest

from bench import check, run as run_mod

SEED = 2 ** 31 + 77
# The CPU multiplies float32 operands exactly: that is the precision this
# configuration states, and its control goes one below (bfloat16).  Its
# limits come from its own readings on three seeds (build sandbox CPU):
# the program read proj 1.5e-7..2.4e-7, heads 4.2e-6..6.2e-6, decode
# 7.6e-8..8.3e-8, backproj 2.0e-7..2.5e-7; the control read proj
# 5.0e-3..1.1e-2, heads 0.071..0.237, decode 0.091..0.209, backproj
# 0.010..0.012.
SMALL = {"streams": 2, "erp_hw": [64, 128], "warmup_rounds": 2,
         "frame_pool": 2, "matmul_operands": "float32",
         "limits": {"proj_err": 1e-4, "heads_err": 1e-3, "decode_err": 1e-4,
                    "backproj_err": 1e-4, "nms_flips": 0}}


@pytest.fixture(scope="module")
def session():
    cell = run_mod.load_cell("det2-overload")
    cell["mix"] = dict(cell["mix"], streams=2, fps=0.5)
    # narrow rungs at small crops: a size the CPU holds
    overrides = dict(SMALL, detectors=[
        dict(d, input_size=s, width_mult=w, base_depth=n)
        for d, s, w, n in zip(cell["config"]["detectors"], (64, 96),
                              (0.25, 0.5), (1, 2))])
    return run_mod.Session(cell, SEED, False, cache=False,
                           config_overrides=overrides, log=lambda *_: None)


@pytest.fixture(scope="module")
def clean(session):
    return session.window(SEED, 4.0), session.last


def test_the_last_line_has_exactly_the_contract_keys(clean):
    out, _ = clean
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"frames_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(out["checks"]) == set(check.NAMES)
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


def test_the_control_is_not_correct(session, clean):
    _, (served, nms, params_ref) = clean
    control = check.control_numbers(served, nms, session.config, SEED,
                                    params_ref)
    good = check.served_numbers(served, nms, session.config, SEED,
                                params_ref)
    assert not check.verdict(control, session.config["limits"])
    assert control["heads_err"] > session.config["limits"]["heads_err"]
    for name in ("proj_err", "heads_err", "decode_err", "backproj_err"):
        assert control[name] > 3 * good[name], name


def _fault_projection(monkeypatch, pod):
    from repro.kernels.gnomonic import ops

    real = ops.project_srois_batched
    monkeypatch.setattr(ops, "project_srois_batched",
                        lambda *a, **k: real(*a, **k)[:, ::-1])
    pod.backend._crop_cache.clear()
    return "proj_err"


def _fault_weights(monkeypatch, pod):
    from repro.models import detector as det_mod

    other = det_mod.init_params(jax.random.PRNGKey(99), pod.backend.cfgs[0])
    monkeypatch.setattr(pod.backend, "params",
                        [other] + pod.backend.params[1:])
    return "heads_err"


def _fault_half_batch(monkeypatch, pod):
    from repro.models import detector as det_mod

    real = det_mod.decode

    def decode(outs, cfg, conf, max_det=128, valid=None):
        b = outs[0].shape[0]
        valid = np.arange(b) < max(1, b // 2)
        return real(outs, cfg, conf, max_det=max_det, valid=valid)

    monkeypatch.setattr(det_mod, "decode", decode)
    monkeypatch.setattr(pod.backend, "_jit_cache", {})
    return "decode_err"


def _fault_backprojection(monkeypatch, pod):
    from repro.serving import scheduler

    real = scheduler.pi_box_to_sphbb
    monkeypatch.setattr(scheduler, "pi_box_to_sphbb",
                        lambda *a: real(*a) + jax.numpy.array([0.05, 0, 0, 0]))
    return "backproj_err"


def _fault_nms(monkeypatch, pod):
    from repro.serving import server

    real = server.sph_nms_batch

    def nms(boxes, scores, mask=None, **k):
        keep = real(boxes, scores, mask, **k)
        keep[:, 0] = ~keep[:, 0]
        return keep

    monkeypatch.setattr(server, "sph_nms_batch", nms)
    monkeypatch.setattr(pod.server, "incremental_nms", False)
    return "nms_flips"


@pytest.mark.parametrize("fault", [
    _fault_projection, _fault_weights, _fault_half_batch,
    _fault_backprojection, _fault_nms])
def test_a_planted_fault_is_not_correct(session, clean, fault, monkeypatch):
    number = fault(monkeypatch, session.pod)
    out = session.window(SEED + 1, 2.0)
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] > c["limit"], (number, c)
