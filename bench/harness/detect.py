"""A detection cell: the program's ``VisionEngine`` serving a YOLO model.

The vision cell (``harness.vision``) makes the weights and the image pool
from the seed and serves the backlog; this cell serves images already
letterboxed onto the model's canvas, sums the engine's own fetch counts
over the window, and compares each served image's three detection maps
with the plain reference.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, vision
from harness import traffic as TR

REF_BLOCK = 16                 # images per reference call


class Cell(vision.Cell):
    def __init__(self, cfg: dict, traffic: dict, seed: int, bench=spec.BENCH):
        canvas = [cfg["height"], cfg["width"]]
        if any(list(g) != canvas for g in traffic["geometries"]):
            raise ValueError(f"a detection cell serves images already on the "
                             f"model's {canvas} canvas, got "
                             f"{traffic['geometries']}")
        # the vision cell reads the image channels as ``num_channels``
        super().__init__({**cfg, "num_channels": cfg["channels"]}, traffic,
                         seed, bench)
        ch = spec.model(cfg["family"], bench).map_channels(cfg)
        self.maps = {name: (cfg["height"] // s, cfg["width"] // s, ch)
                     for name, s in (("det1", 32), ("det2", 16),
                                     ("det3", 8))}

    def run(self, seconds: float) -> dict:
        """Serve the backlog as the vision cell does, and sum each call's
        ``fetch_s`` and ``d2h_bytes`` (left out where the program counts
        no ``d2h_bytes``)."""
        per_call = int(self.traffic["max_per_call"])
        queue = TR.backlog(self.traffic, self.seed)
        fetch_s, d2h = 0.0, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            batch = [next(queue) for _ in range(per_call)]
            outs = self.engine.infer([self._request(g, i) for g, i in batch])
            st = self.engine.last_stats
            fetch_s += st["fetch_s"]
            d2h.append(st.get("d2h_bytes"))
            self.served += [vision.Served(g, i, out)
                            for (g, i), out in zip(batch, outs)]
        self.stats = {"window_s": time.perf_counter() - t0,
                      "images": len(self.served),
                      "attempted": len(self.served), "fetch_s": fetch_s}
        if d2h and None not in d2h:
            self.stats["d2h_bytes"] = sum(d2h)
        return self.stats

    # ------------------------------------------------------------- checks

    def _bad(self, out) -> bool:
        return (not isinstance(out, dict) or set(out) != set(self.maps)
                or any(np.shape(out[k]) != shp
                       or not np.isfinite(out[k]).all()
                       for k, shp in self.maps.items()))

    def reference_outputs(self, keys, ref, *, precision=None,
                          block: int = REF_BLOCK) -> dict:
        """The reference's maps for each (geometry, image) in ``keys``,
        float32 at ``precision`` (the highest by default; a lower one gives
        the control), ``block`` images per call."""
        fwd = jax.jit(functools.partial(
            ref.forward, precision=precision or ref.HIGHEST))
        params = jax.tree.map(lambda a: a.astype(jnp.float32), self.params)
        keys = sorted(set(keys))
        out = {}
        for lo in range(0, len(keys), block):
            chunk = np.stack([self.pool[g][i] for g, i in keys[lo:lo + block]])
            pad = block - len(chunk)
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
            maps = jax.device_get(fwd(params, chunk))
            for j, key in enumerate(keys[lo:lo + block]):
                out[key] = {k: v[j] for k, v in maps.items()}
        return out

    def check(self, ref, *, sample: int, control=None) -> dict:
        """The numbers compared: ``max_rel_err``, the worst over a seeded
        sample of served images and over the three scales of
        max|served - reference| / max|reference| of one map, and that
        worst for each scale.  With ``control`` (a lower matmul precision)
        the reference computed at that precision takes the served maps'
        place."""
        bad = sum(1 for s in self.served if self._bad(s.output))
        if bad or not self.served:
            return {"served": len(self.served), "failed": bad,
                    "max_rel_err": math.inf}
        idx = self.sample(sample)
        want = self.reference_outputs(
            [(self.served[i].geometry, self.served[i].image) for i in idx],
            ref)
        low = (self.reference_outputs(list(want), ref, precision=control)
               if control is not None else None)
        worst = dict.fromkeys(self.maps, 0.0)
        for i in idx:
            s = self.served[i]
            key = (s.geometry, s.image)
            got = s.output if low is None else low[key]
            for k in self.maps:
                w = np.asarray(want[key][k], np.float64)
                err = np.abs(np.asarray(got[k], np.float64) - w).max()
                worst[k] = max(worst[k], float(err / np.abs(w).max()))
        return {"served": len(self.served), "failed": 0, "checked": len(idx),
                "max_rel_err": max(worst.values()),
                **{f"max_rel_err.{k}": v for k, v in worst.items()}}
