"""The YOLO family as the program builds it: YOLOv3 (Redmon & Farhadi 2018,
arXiv:1804.02767; layers as in darknet's cfg/yolov3.cfg), a Darknet-53
backbone and three detection heads, BatchNorm folded into each conv.

A configuration of another activation, slope, layout or filter
progression is refused, never built as this one.  A width multiplier is
accepted only where every filter count scales together from the published
32/64/.../1024.
"""
from __future__ import annotations

import math

STEM = 32                  # the published stem width; each stage doubles it


def _check(cfg: dict) -> float:
    """The width multiplier of ``cfg``, once its keys are this family's."""
    want = {"activation": "leaky", "leaky_slope": 0.1}
    for key, value in want.items():
        if cfg.get(key) != value:
            raise ValueError(f"the program builds YOLOv3 with "
                             f"{key}={value!r}, the file states "
                             f"{cfg.get(key)!r}")
    filters, repeats = list(cfg["backbone_filters"]), cfg["backbone_repeats"]
    if len(filters) != 6 or len(repeats) != 5:
        raise ValueError(f"Darknet-53 has a stem and five stages; the file "
                         f"states {len(filters)} filter counts and "
                         f"{len(repeats)} repeat counts")
    width = filters[0] / STEM
    # the program rounds each width as round(c * width), at least 4, and a
    # residual block's narrow conv as half its stage's width, at least 4
    sizes = [max(4, round(STEM * 2 ** i * width)) for i in range(6)]
    if filters != sizes or filters[1] < 8:
        raise ValueError(f"backbone_filters {filters} are not the Darknet-53 "
                         f"widths 32*2^i*{width} = {sizes} (each at least 8 "
                         f"past the stem)")
    for key in ("height", "width"):
        if cfg[key] % 32:
            raise ValueError(f"{key} {cfg[key]} is not a multiple of the "
                             f"coarsest stride, 32")
    return width


def program_config(cfg: dict):
    """The program's ``VisionConfig`` for configuration file ``cfg``."""
    from repro.vision.models import VisionConfig
    width = _check(cfg)
    return VisionConfig(name=cfg["name"], arch="yolov3",
                        input_hw=(cfg["height"], cfg["width"]),
                        in_channels=cfg["channels"],
                        num_classes=cfg["classes"], width_mult=width,
                        stage_blocks=tuple(cfg["backbone_repeats"]),
                        anchors_per_scale=cfg["anchors_per_scale"],
                        param_dtype=cfg["torch_dtype"])


def map_channels(cfg: dict) -> int:
    """Channels of each detection map: anchors x (box, objectness, classes)."""
    return cfg["anchors_per_scale"] * (5 + cfg["classes"])


def param_shapes(cfg: dict) -> dict:
    """Shapes of the weights, in the program's pytree layout: each conv's
    filter ``(kh, kw, Cin, Cout)``."""
    _check(cfg)
    f = cfg["backbone_filters"]
    shapes = {"stem": (3, 3, cfg["channels"], f[0]), "stages": []}
    for si, reps in enumerate(cfg["backbone_repeats"]):
        c_in, c = f[si], f[si + 1]
        shapes["stages"].append({
            "down": (3, 3, c_in, c),
            "res": [{"a": (1, 1, c, c // 2), "b": (3, 3, c // 2, c)}
                    for _ in range(reps)]})

    def head(c_in, narrow, wide):
        return {"pairs": [{"a": (1, 1, c_in if r == 0 else wide, narrow),
                           "b": (3, 3, narrow, wide)} for r in range(3)],
                "det": (1, 1, wide, map_channels(cfg))}

    shapes["head1"] = head(f[5], f[4], f[5])
    shapes["up1"] = (1, 1, f[4], f[3])
    shapes["head2"] = head(f[3] + f[4], f[3], f[4])
    shapes["up2"] = (1, 1, f[3], f[2])
    shapes["head3"] = head(f[2] + f[3], f[2], f[3])
    return shapes


def _filters(tree):
    if isinstance(tree, tuple):
        yield tree
    else:
        for sub in (tree.values() if isinstance(tree, dict) else tree):
            yield from _filters(sub)


def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image (every conv), from the
    configuration's shapes: each conv's filter size times its output's
    pixels, at the stride its part of the network runs at."""
    shapes = param_shapes(cfg)
    strides = {"stem": 1, "head1": 32, "up1": 32, "head2": 16, "up2": 16,
               "head3": 8}
    parts = [(shapes[k], s) for k, s in strides.items()]
    parts += [(st, 2 ** (i + 1)) for i, st in enumerate(shapes["stages"])]
    return sum((cfg["height"] // s) * (cfg["width"] // s)
               * sum(math.prod(w) for w in _filters(tree))
               for tree, s in parts)
