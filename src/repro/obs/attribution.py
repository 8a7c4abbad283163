"""Measured-vs-modeled attribution: join the op ring against wall scopes.

The op ring records the *modeled* side of every eager dispatch (FLOPs =
2*B*M*K*N, HBM operand traffic from the Fig. 11 model, DRAM energy); with
``optrace.configure(measure_dispatch=True)`` the dispatcher also times
each eager kernel call through ``jax.block_until_ready`` and records a
``dispatch:<kind>`` wall scope -- the *measured* side.  This module joins
the two per kernel kind per backend and reports:

  * achieved FLOP/s and achieved bytes/s (modeled volume / measured wall);
  * roofline placement against the running device's published peaks
    (:func:`repro.core.hw.chip_for`; ridge point = peak_flops / hbm_bw;
    modeled time = max(compute, traffic));
  * modeled-vs-measured time error (``measured_wall_s / modeled_time_s``).

A CPU run has no chip peaks: its roofline fields read "not measured"
rather than placing CPU walls against a TPU's peaks.

Jitted steps never hit the ring (one dispatch per compilation), so their
modeled cost arrives via ``optrace.traced_costs()``; the serve/vision
engines difference those totals around each step call and surface an
aggregate achieved-intensity row in ``last_stats`` (see
:func:`engine_row`).
"""
from __future__ import annotations

import json
from typing import Any

from repro.core.hw import ChipSpec, chip_for
from repro.obs import optrace

# wall scopes recorded by the dispatcher under measure_dispatch
WALL_PREFIX = "dispatch:"
NOT_MEASURED = "not measured"


def _chip(chip: ChipSpec | None) -> ChipSpec | None:
    """``chip``, or the running device's peaks (None on the CPU)."""
    return chip if chip is not None else chip_for()


def _chip_info(chip: ChipSpec | None) -> dict[str, Any] | str:
    if chip is None:
        return NOT_MEASURED
    return {"name": chip.name, "peak_flops": chip.peak_flops,
            "hbm_bw": chip.hbm_bw,
            "ridge_flops_per_byte": chip.peak_flops / chip.hbm_bw}


def _roofline(flops: float, nbytes: float, chip: ChipSpec | None
              ) -> tuple[float | None, str]:
    """(modeled seconds, placement) for a modeled (flops, bytes) volume;
    (None, "not measured") without chip peaks."""
    if chip is None:
        return None, NOT_MEASURED
    t_compute = flops / chip.peak_flops if flops else 0.0
    t_traffic = nbytes / chip.hbm_bw if nbytes else 0.0
    placement = "compute-bound" if t_compute >= t_traffic else "memory-bound"
    return max(t_compute, t_traffic), placement


def measured_walls() -> dict[tuple[str, str], dict[str, float]]:
    """Summed ``dispatch:<kind>`` wall scopes keyed by (kind, backend)."""
    out: dict[tuple[str, str], dict[str, float]] = {}
    for s in optrace.spans():
        if s.cat != "wall" or not s.name.startswith(WALL_PREFIX):
            continue
        kind = s.name[len(WALL_PREFIX):]
        backend = str(s.args.get("backend", ""))
        row = out.setdefault((kind, backend), {"wall_s": 0.0, "calls": 0})
        row["wall_s"] += s.dur_s
        row["calls"] += 1
    return out


def kind_rows(chip: ChipSpec | None = None) -> list[dict[str, Any]]:
    """One attribution row per (kind, backend) seen in the op ring.

    Under ring sampling the modeled sums cover only the sampled events;
    ``sample_coverage`` reports the sampled fraction so consumers can
    scale (the wall scopes are *not* sampled -- they come from the
    measured side)."""
    groups: dict[tuple[str, str], dict[str, float]] = {}
    for ev in optrace.events():
        key = (ev.kind, ev.backend or "")
        g = groups.setdefault(key, {"count": 0, "flops": 0.0,
                                    "bytes": 0.0, "energy_j": 0.0})
        g["count"] += 1
        g["flops"] += ev.flops
        g["bytes"] += ev.bytes
        g["energy_j"] += ev.energy_j
    walls = measured_walls()
    # a measured kind whose ring events were sampled away still gets a row
    for key in walls:
        groups.setdefault(key, {"count": 0, "flops": 0.0,
                                "bytes": 0.0, "energy_j": 0.0})
    chip = _chip(chip)
    rows = []
    for (kind, backend), g in sorted(groups.items()):
        modeled_t, placement = _roofline(g["flops"], g["bytes"], chip)
        w = walls.get((kind, backend))
        row: dict[str, Any] = {
            "kind": kind,
            "backend": backend,
            "count": int(g["count"]),
            "modeled_flops": g["flops"],
            "modeled_bytes": g["bytes"],
            "modeled_energy_j": g["energy_j"],
            "modeled_time_s": modeled_t,
            "roofline": placement if g["bytes"] or g["flops"] else None,
            "intensity_flops_per_byte":
                g["flops"] / g["bytes"] if g["bytes"] else None,
            "measured_wall_s": w["wall_s"] if w else None,
            "measured_calls": w["calls"] if w else 0,
            "achieved_flops_per_s": None,
            "achieved_bytes_per_s": None,
            "time_error_ratio": None,
        }
        if w and w["wall_s"] > 0:
            row["achieved_flops_per_s"] = g["flops"] / w["wall_s"]
            row["achieved_bytes_per_s"] = g["bytes"] / w["wall_s"]
            if modeled_t:
                row["time_error_ratio"] = w["wall_s"] / modeled_t
        rows.append(row)
    return rows


def report(chip: ChipSpec | None = None) -> dict[str, Any]:
    """The full attribution report (what ``attribution.json`` holds)."""
    chip = _chip(chip)
    rows = kind_rows(chip)
    total = _totals(rows)
    return {
        "chip": _chip_info(chip),
        "kinds": rows,
        "totals": total,
        "traced": {f"{op}:{kind}": cost for (op, kind), cost
                   in sorted(optrace.traced_costs().items())},
        "ring_events": len(optrace.events()),
        "dropped_ops": optrace.dropped_ops(),
        "sampled_out_ops": optrace.sampled_out_ops(),
        "sample_every": optrace.sample_every(),
    }


def _totals(rows: list[dict[str, Any]]) -> dict[str, Any]:
    tot = {"modeled_flops": 0.0, "modeled_bytes": 0.0,
           "modeled_energy_j": 0.0, "measured_wall_s": 0.0}
    for r in rows:
        tot["modeled_flops"] += r["modeled_flops"]
        tot["modeled_bytes"] += r["modeled_bytes"]
        tot["modeled_energy_j"] += r["modeled_energy_j"]
        tot["measured_wall_s"] += r["measured_wall_s"] or 0.0
    return tot


def write_json(path: str, chip: ChipSpec | None = None) -> dict[str, Any]:
    rep = report(chip)
    with open(path, "w") as f:
        json.dump(rep, f, indent=1, sort_keys=True)
    return rep


def engine_row(*, wall_s: float, modeled: dict[str, float], steps: int,
               covered_steps: int, chip: ChipSpec | None = None
               ) -> dict[str, Any]:
    """The achieved-intensity row engines put in ``last_stats``.

    ``modeled`` sums per-execution step cost reconstructed from the
    traced-cost ledger (see the module docstring); ``covered_steps`` is
    how many executed steps had a known per-trace cost -- steps whose
    signature was traced before telemetry was enabled contribute wall
    time but no modeled volume, and the coverage ratio says so.
    """
    chip = _chip(chip)
    flops = modeled.get("flops", 0.0)
    nbytes = modeled.get("bytes", 0.0)
    modeled_t, placement = _roofline(flops, nbytes, chip)
    row: dict[str, Any] = {
        "modeled_flops": flops,
        "modeled_bytes": nbytes,
        "modeled_energy_j": modeled.get("energy_j", 0.0),
        "modeled_time_s": modeled_t,
        "roofline": placement if (flops or nbytes) else None,
        "intensity_flops_per_byte": flops / nbytes if nbytes else None,
        "measured_wall_s": wall_s,
        "achieved_flops_per_s": flops / wall_s if wall_s > 0 else None,
        "achieved_bytes_per_s": nbytes / wall_s if wall_s > 0 else None,
        "time_error_ratio":
            wall_s / modeled_t if wall_s > 0 and modeled_t else None,
        "modeled_step_coverage":
            covered_steps / steps if steps else 0.0,
        "chip": chip.name if chip is not None else NOT_MEASURED,
    }
    return row


def paper_section(chip: ChipSpec | None = None) -> dict[str, Any]:
    """The ``paper_report["attribution"]`` section: measured kinds only.

    The analytic paper report stands on its own; this section tethers it
    to measurement when telemetry carries any, and says why not when it
    does not."""
    chip = _chip(chip)
    rows = [r for r in kind_rows(chip) if r["measured_wall_s"]]
    if not rows:
        return {"available": False,
                "reason": "no measured dispatch walls; enable repro.obs "
                          "and optrace.configure(measure_dispatch=True), "
                          "then run the workload eagerly"}
    return {"available": True, "chip": _chip_info(chip), "kinds": rows}
