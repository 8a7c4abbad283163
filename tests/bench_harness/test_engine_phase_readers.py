"""The readers of the engine-phase metrics on hand-built contexts.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

``idle_fetch.backlog`` and ``idle_admit.backlog`` read the idle seconds the
trace reduction gives to the program's ``vision.*`` spans;
``setup_compile_s`` reads the program's compile counter.
"""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import pytest

from harness import spec

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
IDLE = {"idle_fetch.backlog": "vision.fetch",
        "idle_admit.backlog": "vision.admit"}


def _ctx(idle_gaps, window_s=10.0):
    return {"trace": {"window_s": window_s, "busy_s": 2.5,
                      "idle_gaps": idle_gaps}}


@pytest.mark.parametrize("metric", sorted(IDLE))
def test_idle_reader_reads_its_span(metric):
    read = spec.reader(metric, BENCH)
    gaps = [["vision.fetch", 6.0], ["np.asarray(jax.Array)", 0.5],
            ["vision.admit", 0.9]]
    want = {"vision.fetch": 60.0, "vision.admit": 9.0}[IDLE[metric]]
    assert read(_ctx(gaps)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(IDLE))
def test_idle_reader_reads_zero_when_only_other_spans_are_named(metric):
    other = "vision.admit" if IDLE[metric] == "vision.fetch" \
        else "vision.fetch"
    gaps = [[other, 3.0], ["ReadSyncFlag", 0.4]]
    assert spec.reader(metric, BENCH)(_ctx(gaps)) == 0.0


@pytest.mark.parametrize("metric", sorted(IDLE))
def test_idle_reader_is_silent_without_program_spans(metric):
    gaps = [["np.asarray(jax.Array)", 5.39],
            ["PjitFunction(dynamic_slice)", 0.89]]
    read = spec.reader(metric, BENCH)
    assert read(_ctx(gaps)) is None
    assert read(_ctx([])) is None


@pytest.mark.parametrize("metric", sorted(IDLE))
def test_idle_reader_is_silent_without_a_trace(metric):
    assert spec.reader(metric, BENCH)({"trace": None}) is None


def test_setup_compile_reader_sums_the_program_counter():
    from repro.obs import metrics
    read = spec.reader("setup_compile_s", BENCH)
    metrics.clear()
    try:
        # no compile since the registry was emptied: nothing to read
        assert read({"trace": None}) is None

        def bench_compile_probe(x):
            return x * 7.0 + 2.0

        jax.jit(bench_compile_probe)(jnp.ones((2, 9)))
        got = read({"trace": None})
        rows = metrics.snapshot()["jax_compile_seconds_total"]["values"]
        assert "jit(bench_compile_probe)" in {r["labels"]["fun"] for r in rows}
        assert got == pytest.approx(sum(r["value"] for r in rows)) and got > 0
    finally:
        metrics.clear()
