"""The benchmark's FLOP count against XLA's own count of the forward.

XLA's ``cost_analysis`` of ``detector.apply`` counts the convolutions
(taps on the zero border left out, as here) plus the elementwise work
of GroupNorm, Mish and the upsampling, which the benchmark leaves out.
That elementwise share is 2-8% at these sizes, so XLA's count must lie
between the benchmark's and 10% above it.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.flops import forward_flops
from repro.models import detector as det_mod

KEYS = ("name", "input_size", "width_mult", "depth_mult", "n_classes", "p6",
        "base_width", "base_depth")
# the program's whole Table II ladder, as configuration entries
LADDER = [{k: v for k, v in dataclasses.asdict(c).items() if k in KEYS}
          for c in det_mod.PAPER_LADDER]
DET2 = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "det2.json").read_text())["detectors"]
CASES = LADDER + DET2
IDS = [d["name"] for d in LADDER] + [f"det2-{d['name']}" for d in DET2]


@pytest.mark.parametrize("d", CASES, ids=IDS)
def test_flops_match_xla_cost_analysis(d):
    d = dict(d, input_size=64)
    cfg = det_mod.DetectorConfig(**d)
    params = jax.eval_shape(
        lambda: det_mod.init_params(jax.random.PRNGKey(0), cfg))
    compiled = jax.jit(lambda p, x: det_mod.apply(p, x, cfg)).lower(
        params, jax.ShapeDtypeStruct((1, 64, 64, 3), jnp.float32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = cost["flops"] / forward_flops(d)
    assert 1.0 <= ratio <= 1.10, ratio


def test_ladder_matches_the_program_ladder():
    assert [det_mod.DetectorConfig(**d) for d in LADDER] == \
        list(det_mod.PAPER_LADDER)
    # a larger rung costs more, p6-1280 about 200x tiny-416
    f = [forward_flops(d) for d in LADDER]
    assert f == sorted(f) and 150 < f[-1] / f[0] < 300


def test_a_border_tap_is_not_counted():
    d = dataclasses.asdict(det_mod.PAPER_LADDER[0])
    d = {k: d[k] for k in KEYS}
    small, big = dict(d, input_size=32), dict(d, input_size=64)
    # the border's share shrinks with size: more than 4x the work at 2x
    assert forward_flops(big) > 4 * forward_flops(small)
