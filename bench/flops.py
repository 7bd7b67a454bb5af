"""Operations of one detector forward, counted from the configuration.

Every convolution of the CSP detector (stem, backbone stages, FPN,
heads): two operations per multiply-add of a kernel tap that lands
inside the input (SAME padding; taps on the zero border do no useful
work).  GroupNorm, Mish, the upsampling and the decode are left out:
elementwise work that the MXU does not do and a few percent of the
total (``bench/tests`` bounds the gap to XLA's own count).
"""

from __future__ import annotations

import math

from bench.reference import _depth, _width, strides


def forward_flops(d: dict) -> float:
    """FLOPs of one image through the detector ``d`` (a configuration
    file's ``detectors`` entry)."""
    total = 0.0
    res = d["input_size"]

    def conv(h, k, cin, cout, stride=1):
        nonlocal total
        out = math.ceil(h / stride)
        lo = max((out - 1) * stride + k - h, 0) // 2
        taps = sum(1 for o in range(out) for t in range(k)
                   if 0 <= o * stride + t - lo < h)
        total += 2.0 * taps * taps * cin * cout
        return out

    def csp(h, c, n):
        half = c // 2
        conv(h, 1, c, half)
        conv(h, 1, c, half)
        for _ in range(n):
            conv(h, 1, half, half)
            conv(h, 3, half, half)
        conv(h, 1, c, c)

    chans = [_width(d, 2 ** (i + 1)) for i in range(len(strides(d)))]
    res = conv(res, 3, 3, _width(d, 1), 2)
    res = conv(res, 3, _width(d, 1), chans[0] // 2, 2)
    sizes = []
    c_prev = chans[0] // 2
    for c in chans:
        res = conv(res, 3, c_prev, c, 2)
        csp(res, c, _depth(d))
        sizes.append(res)
        c_prev = c
    for i in reversed(range(len(chans) - 1)):
        conv(sizes[i + 1], 1, chans[i + 1], chans[i])
        csp(sizes[i], chans[i], max(1, _depth(d) // 2))
    for c, h in zip(chans, sizes):
        conv(h, 3, c, c)
        conv(h, 1, c, 5 + d["n_classes"])
    return total
