"""Plain reference for the YOLO family: YOLOv3 (Redmon & Farhadi 2018,
arXiv:1804.02767) as darknet's cfg/yolov3.cfg lays it out, layer by layer.

Darknet-53: a 3x3/32 conv, then five stages, each a 3x3 stride-2 conv and
residual blocks of a 1x1 to half width and a 3x3 back, added to the
block's input with no activation after the sum ([shortcut] from=-3,
linear).  Then three heads, at strides 32, 16 and 8: (1x1, 3x3) x2, a 1x1
whose output is the route tap, a 3x3 and a linear 1x1 with a bias.  The
route tap of the stride-32 head goes through a 1x1, a nearest 2x upsample
and is concatenated, in the order [upsampled, skip], with the last
stride-16 backbone output (cfg layer 61); the stride-16 head's tap likewise
with the last stride-8 output (cfg layer 36).  Every other conv is
followed by leaky ReLU with slope 0.1.  Plain ``jax.lax``/``jnp`` at the
precision asked for; nothing of the program under test is imported.

Departures from the darknet cfg:

* BatchNorm is folded into each conv's weight and bias (inference), where
  darknet keeps a conv without bias and a BatchNorm after it.
* The [yolo] layers (sigmoid and exp of the box terms, anchors) and NMS
  are left out: the outputs are the three raw maps.
* Maps are NHWC, channel ``a * (5 + classes) + k`` for anchor ``a`` and
  term ``k``, where darknet holds (channels, h, w).
* Inputs are images already letterboxed onto the canvas, float32 in
  [0, 1]; the letterbox itself is not part of the forward pass.

``params`` is the pytree the benchmark made, each conv ``{"w": (kh, kw,
Cin, Cout), "b": (Cout,)}``:

    {"stem", "stages": [{"down", "res": [{"a", "b"}, ...]}, ...],
     "head1"/"head2"/"head3": {"pairs": [{"a", "b"}] * 3, "det"},
     "up1", "up2"}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
INT8 = "int8"
SLOPE = 0.1


def _int8(x, axes):
    """Symmetric int8 rounding with one scale per slice over ``axes``,
    returned dequantized (float32)."""
    scale = jnp.maximum(jnp.abs(x).max(axis=axes, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _product(f, x, w, precision):
    """``f(x, w)`` with float32 accumulation: "highest" multiplies in
    float32; "int8" (the lower-precision control) first rounds the
    activations to int8 with one scale per tensor and the weights with one
    scale per output channel, as an int8 deployment would."""
    if precision == INT8:
        x = _int8(x, tuple(range(x.ndim)))
        w = _int8(w, tuple(range(w.ndim - 1)))
    elif precision != HIGHEST:
        raise ValueError(f"unknown precision {precision!r}")
    return f(x, w, jax.lax.Precision.HIGHEST)


def _conv(p, x, stride, precision):
    """Darknet's conv: ``pad=1`` is ``size // 2`` on every side."""
    k = p["w"].shape[0]

    def f(a, b, prec):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(k // 2, k // 2)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=jnp.float32)
    y = _product(f, x.astype(jnp.float32), p["w"].astype(jnp.float32),
                 precision)
    return y + p["b"].astype(jnp.float32)


def _leaky(x):
    return jnp.where(x > 0, x, SLOPE * x)


def _upsample(x):
    """Darknet's [upsample] stride=2: each pixel repeated 2x2."""
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def forward(params, x, *, precision=HIGHEST):
    """``{"det1", "det2", "det3"}``: the maps at strides 32, 16 and 8, each
    (N, h, w, anchors * (5 + classes)) in float32, for images ``x``
    (N, H, W, C), with every product at ``precision`` ("highest" for the
    reference, "int8" for the lower-precision control) and float32
    accumulation."""
    def conv(p, h, stride=1):
        return _leaky(_conv(p, h, stride, precision))

    h = conv(params["stem"], x)
    skips = []
    for stage in params["stages"]:
        h = conv(stage["down"], h, 2)
        for blk in stage["res"]:
            h = h + conv(blk["b"], conv(blk["a"], h))
        skips.append(h)

    def head(hp, h):
        for r, pair in enumerate(hp["pairs"]):
            h = conv(pair["a"], h)
            if r == 2:
                route = h
            h = conv(pair["b"], h)
        return route, _conv(hp["det"], h, 1, precision)

    route, det1 = head(params["head1"], h)
    h = jnp.concatenate([_upsample(conv(params["up1"], route)), skips[3]],
                        axis=-1)
    route, det2 = head(params["head2"], h)
    h = jnp.concatenate([_upsample(conv(params["up2"], route)), skips[2]],
                        axis=-1)
    _, det3 = head(params["head3"], h)
    return {"det1": det1, "det2": det2, "det3": det3}
