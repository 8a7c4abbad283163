"""The detection cell (``bench/harness/detect.py``) and the YOLO family
(``bench/models/yolo.py``, ``bench/references/yolo.py``) on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

A toy YOLOv3 cell (an eighth of the widths, one block per stage, 64x64,
8 classes, 4 lanes) is added only as files and ``BENCHMARK.json``
entries, as a later cell would be.  It runs through ``run.run`` and is
correct; the int8 control and planted faults in the timed path are not.
The family's work count and weight layout are checked against the
program at the published size, from shapes alone.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil

import jax
import numpy as np
import pytest

from harness import spec

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "bench"
CELL = "toy-yolo.toy-backlog-64"
# toy limit, from toy readings on the CPU: the program matches its float32
# reference to about 1e-6; the int8 control and the faults read 1e-3 or more
TOY_LIMITS = {"max_rel_err": 1e-4}
TOY_TRAFFIC = {"kind": "images", "arrival": {"process": "backlog"},
               "geometries": [[64, 64]], "pool_per_geometry": 5,
               "max_per_call": 4, "check_sample": 100}


def _toy_yolo():
    cfg = json.loads((BENCH / "configs" / "yolov3.json").read_text())
    cfg.update(name="toy-yolo", backbone_filters=[4, 8, 16, 32, 64, 128],
               backbone_repeats=[1] * 5, width=64, height=64, classes=8,
               engine={"kind": "detect", "batch_slots": 4})
    return cfg


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A checkout holding the real harness plus a toy detection cell added
    only as files and ``BENCHMARK.json`` entries."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    (bench / "configs" / "toy-yolo.json").write_text(json.dumps(_toy_yolo()))
    (bench / "traffic" / "toy-backlog-64.json").write_text(
        json.dumps(TOY_TRAFFIC))
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(TOY_LIMITS))
    full = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    full["workloads"].append({"name": CELL, "config": "toy-yolo",
                              "traffic": "toy-backlog-64", "chips": 1,
                              "why": "toy"})
    for m in full["end_to_end"] + full["per_layer"]:
        if m["name"] in ("img_per_s", "fetch_gbps.detect"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(full))
    return root


def _run(root, *, seconds=0.5):
    import run as R
    args = argparse.Namespace(workload=CELL, seed=2**32 + 17,
                              seconds=seconds, trace=0)
    return R.run(args, bench=root / "bench", root=root, check_chips=False)


def _cell(root, seed=3, seconds=0.3):
    """The toy cell built, warmed up, served for ``seconds`` and freed."""
    import run as R
    bench = root / "bench"
    w = spec.workload(CELL, spec.benchmark(root))
    cfg = spec.config(w["config"], bench)
    c = R.make_cell(cfg, spec.traffic(w["traffic"], bench), seed, bench)
    c.warm_up()
    c.run(seconds)
    c.free()
    return c, spec.reference(cfg["family"], bench)


@pytest.fixture(scope="module")
def served(toy_root):
    return _cell(toy_root)


def _correct(checks):
    import run as R
    return R.verdict(checks, TOY_LIMITS)[0]


# ------------------------------------------------- the family, by shapes


def test_yolov3_macs_match_the_program_and_the_paper_count():
    from repro.vision import trace
    cfg = spec.config("yolov3")
    model = spec.model("yolo")
    traced = trace.trace_model(model.program_config(cfg))
    assert len(traced) == 75
    assert model.macs_per_image(cfg) == sum(r.macs for r in traced) \
        == 32_932_037_632


@pytest.mark.parametrize("cfg", [spec.config("yolov3"), _toy_yolo()],
                         ids=["yolov3", "toy"])
def test_param_shapes_are_the_programs_pytree(cfg):
    from repro.vision import models
    model = spec.model("yolo")
    pcfg = model.program_config(cfg)
    got = jax.eval_shape(lambda k: models.init(k, pcfg),
                         jax.random.PRNGKey(0))
    want = model.param_shapes(cfg)
    assert jax.tree.map(lambda p: p["w"].shape, got,
                        is_leaf=lambda p: isinstance(p, dict) and "w" in p) \
        == want


@pytest.mark.parametrize("key,value", [
    ("activation", "relu"), ("leaky_slope", 0.2),
    ("backbone_filters", [32, 64, 128, 256, 512, 512]),
    ("backbone_filters", [2, 4, 8, 16, 32, 64]), ("width", 400)])
def test_a_yolo_configuration_the_family_does_not_build_is_refused(key,
                                                                    value):
    cfg = {**spec.config("yolov3"), key: value}
    model = spec.model("yolo")
    for f in (model.program_config, model.param_shapes, model.macs_per_image):
        with pytest.raises(ValueError):
            f(cfg)


def test_program_maps_match_the_reference_at_toy_size():
    from harness import vision
    from repro.vision import models
    cfg = _toy_yolo()
    model, ref = spec.model("yolo"), spec.reference("yolo")
    pcfg = model.program_config(cfg)
    params = vision.make_params(model.param_shapes(cfg), 5, pcfg.pdtype)
    x = jax.random.uniform(jax.random.PRNGKey(1), (3, 64, 64, 3))
    got = jax.device_get(jax.jit(lambda p, x: models.apply(p, x, pcfg))(
        params, x))
    want = jax.device_get(jax.jit(ref.forward)(params, x))
    assert set(got) == set(want) == {"det1", "det2", "det3"}
    for k, side in (("det1", 2), ("det2", 4), ("det3", 8)):
        assert got[k].shape == want[k].shape == (3, side, side, 39)
        rel = np.abs(got[k] - want[k]).max() / np.abs(want[k]).max()
        assert rel < 1e-5, (k, rel)


def test_a_cell_off_the_canvas_is_refused(toy_root):
    import run as R
    cfg = spec.config("toy-yolo", toy_root / "bench")
    with pytest.raises(ValueError, match="canvas"):
        R.make_cell(cfg, {**TOY_TRAFFIC, "geometries": [[64, 64], [48, 64]]},
                    1, toy_root / "bench")


# ------------------------------------------------- the toy cell, by name


def test_the_toy_detect_cell_added_as_files_runs(toy_root):
    res = _run(toy_root)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"img_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"max_rel_err.det1", "max_rel_err.det2",
            "max_rel_err.det3"} <= set(res["readings"])
    assert res["readings"]["max_rel_err"] == max(
        res["readings"][f"max_rel_err.{k}"] for k in ("det1", "det2", "det3"))


def test_the_window_sums_the_engines_fetch_counts(toy_root, served):
    c, _ = served
    st = c.stats
    # 4 lanes, all full: (2^2 + 4^2 + 8^2) pixels x 39 channels x 4 bytes
    assert st["images"] % 4 == 0
    assert st["d2h_bytes"] == st["images"] * 84 * 39 * 4
    assert st["fetch_s"] > 0
    read = spec.reader("fetch_gbps.detect", toy_root / "bench")
    assert read({"stats": st}) == pytest.approx(
        st["d2h_bytes"] / st["fetch_s"] / 1e9)


def test_a_program_without_d2h_bytes_still_runs(toy_root, monkeypatch):
    """A program that counts no ``d2h_bytes`` (as before the counter was
    added) still gives a complete, correct run, with the reader silent."""
    from repro.vision.engine import VisionEngine
    infer = VisionEngine.infer

    def without(self, requests):
        out = infer(self, requests)
        del self.last_stats["d2h_bytes"]
        return out

    monkeypatch.setattr(VisionEngine, "infer", without)
    c, ref = _cell(toy_root)
    assert "d2h_bytes" not in c.stats and c.stats["fetch_s"] > 0
    assert spec.reader("fetch_gbps.detect", toy_root / "bench")(
        {"stats": c.stats}) is None
    assert _correct(c.check(ref, sample=TOY_TRAFFIC["check_sample"]))


@pytest.mark.parametrize("stats", [
    {"fetch_s": 1.0}, {"fetch_s": 0.0, "d2h_bytes": 10}, {}])
def test_fetch_gbps_reader_is_silent_without_counts(stats):
    assert spec.reader("fetch_gbps.detect", BENCH)({"stats": stats}) is None


# ------------------------------------------------- control and faults


def test_the_int8_control_is_not_correct_at_toy_size(served):
    c, ref = served
    k = TOY_TRAFFIC["check_sample"]
    assert _correct(c.check(ref, sample=k))
    assert not _correct(c.check(ref, sample=k, control="int8"))


class _SwappedConcat:
    """``jax.numpy`` with ``concatenate`` taking its operands reversed."""

    def __getattr__(self, name):
        return getattr(jax.numpy, name)

    @staticmethod
    def concatenate(arrays, axis=0):
        return jax.numpy.concatenate(list(arrays)[::-1], axis=axis)


@pytest.mark.parametrize("fault", ["half_batch", "map_altered",
                                   "fpn_concat_swapped"])
def test_detect_faults_are_not_correct(toy_root, monkeypatch, fault):
    import jax.numpy as jnp
    from repro.vision import models
    apply = models.apply
    if fault == "half_batch":
        def broken(params, x, cfg):
            half = apply(params, x[: x.shape[0] // 2], cfg)
            return jax.tree.map(
                lambda h: jnp.concatenate([h, jnp.broadcast_to(
                    h.mean(0, keepdims=True),
                    (x.shape[0] - h.shape[0],) + h.shape[1:])]), half)
        monkeypatch.setattr(models, "apply", broken)
    elif fault == "map_altered":
        def broken(params, x, cfg):
            out = apply(params, x, cfg)
            return {**out, "det2": out["det2"].at[0, 0, 0, 0].add(1.0)}
        monkeypatch.setattr(models, "apply", broken)
    else:
        # the upsampled route and the backbone skip, concatenated
        # [skip, upsampled]: the same shapes, the weights on wrong channels
        monkeypatch.setattr(models, "jnp", _SwappedConcat())
    assert not _run(toy_root)["correct"]
