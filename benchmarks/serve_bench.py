"""Mixed-length serving benchmark: wave vs continuous vs paged batching.

Runs the same interleaved short/long workload (the shape that triggers wave
batching's head-of-line blocking) through ``WaveServeEngine``, the
continuous ``ServeEngine``, and its paged-cache variants, and emits
``BENCH_serve.json``:

  {"workload": {...},
   "wave":        {"tokens_per_s", "wall_s", "p50/p99_latency_s"},
   "continuous":  {... + "steps", "cache_bytes_per_slot"},
   "paged":       {... + "pool" occupancy/prefix stats},
   "paged_int8":  {...},
   "paged_repeat": {...},    # same prompts again: prefix-cache hits
   "obs": {...},             # tokens/s with telemetry off vs on + overhead %
   "mesh_dp": {...},         # data-parallel mesh over all visible devices
   "speedup_tokens_per_s": ...,
   "cache_reduction_int8_vs_dense_f32": ...}

Latency is per-request completion time from benchmark start (all requests
arrive at t=0).  For the wave engine, every request in a wave completes when
its wave does, so latency is measured per wave group.  The paged rows share
the continuous engine's scheduler -- any throughput delta is pure cache
data movement -- and ``paged_repeat`` replays the identical prompt set so
the prefix index converts prefill steps into page sharing.

Usage:
  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine, WaveServeEngine


def build_workload(cfg, *, n_requests: int, short_len: int, long_len: int,
                   short_new: int, long_new: int, seed: int = 1
                   ) -> list[Request]:
    """Interleave short and long prompts (odd indices are long)."""
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n_requests):
        key, sub = jax.random.split(key)
        plen = long_len if i % 2 else short_len
        mnew = long_new if i % 2 else short_new
        prompt = jax.random.randint(sub, (plen,), 2, cfg.vocab)
        reqs.append(Request(prompt=[int(t) for t in prompt],
                            max_new_tokens=mnew))
    return reqs


def run_wave(engine: WaveServeEngine, reqs) -> dict:
    slots = engine.batch_slots
    lat = np.zeros(len(reqs))
    outs = []
    t0 = time.perf_counter()
    for i in range(0, len(reqs), slots):
        outs.extend(engine.generate(reqs[i: i + slots]))
        lat[i: i + slots] = time.perf_counter() - t0   # wave-granular
    wall = time.perf_counter() - t0
    n_tok = sum(len(o) for o in outs)
    return {
        "tokens": n_tok,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(n_tok / wall, 2),
        "p50_latency_s": round(float(np.percentile(lat, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lat, 99)), 4),
    }


def run_continuous(engine: ServeEngine, reqs) -> dict:
    engine.generate(reqs)
    st = engine.last_stats
    lat = np.array([r["latency_s"] for r in st["requests"]])
    ttft = np.array([r["ttft_s"] for r in st["requests"]])
    out = {
        "tokens": st["generated_tokens"],
        "wall_s": round(st["wall_s"], 4),
        "tokens_per_s": round(st["tokens_per_s"], 2),
        "prefill_tokens_per_s": round(st["prefill_tokens_per_s"], 2),
        "p50_latency_s": round(float(np.percentile(lat, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lat, 99)), 4),
        "p99_ttft_s": round(float(np.percentile(ttft, 99)), 4),
        "steps": st["steps"],
        "prefill_chunk": engine.prefill_chunk,
        "cache_bytes_per_slot": st["cache_bytes_per_slot"],
    }
    if engine.pool is not None:
        out["pool"] = engine.pool.stats()
        out["prefix_hits"] = st["prefix_hits"]
        out["prefix_hit_tokens"] = st["prefix_hit_tokens"]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="yi-9b")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for CI (CPU, seconds not minutes)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=4,
                    help="tokens per KV-cache page for the paged rows")
    ap.add_argument("--out", default="benchmarks/results/BENCH_serve.json")
    ap.add_argument("--check-overhead", action="store_true",
                    help="fail when telemetry overhead exceeds its budget: "
                         "5%% tokens/s for full tracing, 2%% for sampled "
                         "(sample_every=16) mode")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.smoke:
        n_req, short_len, long_len = min(args.requests, 8), 4, 16
        short_new, long_new = 4, 12
    else:
        n_req, short_len, long_len = args.requests, 8, 48
        short_new, long_new = 8, 32
    reqs = build_workload(cfg, n_requests=n_req, short_len=short_len,
                          long_len=long_len, short_new=short_new,
                          long_new=long_new)
    # both cache layouts address the same token capacity: a page pool can
    # only hold whole pages, so round max_len up to a page multiple
    page_size = args.page_size
    max_len = -(-(long_len + long_new + 1) // page_size) * page_size

    wave_engine = WaveServeEngine(params, cfg, batch_slots=args.slots,
                                  max_len=max_len)
    cont_engine = ServeEngine(params, cfg, batch_slots=args.slots,
                              max_len=max_len,
                              prefill_chunk=args.prefill_chunk,
                              cache_dtype="float32")
    # memory/throughput rows: dense-equivalent pool, prefix index off (the
    # apples-to-apples cache-bytes comparison)
    paged_engine = ServeEngine(params, cfg, batch_slots=args.slots,
                               max_len=max_len,
                               prefill_chunk=args.prefill_chunk,
                               paged=True, page_size=page_size,
                               prefix_cache=False)
    int8_engine = ServeEngine(params, cfg, batch_slots=args.slots,
                              max_len=max_len,
                              prefill_chunk=args.prefill_chunk,
                              paged=True, page_size=page_size,
                              cache_fmt="int8", prefix_cache=False)
    # prefix row: 2x pool headroom so registered pages survive admission
    # pressure instead of being evicted before they can ever hit
    pps = -(-max_len // page_size)
    prefix_engine = ServeEngine(params, cfg, batch_slots=args.slots,
                                max_len=max_len,
                                prefill_chunk=args.prefill_chunk,
                                paged=True, page_size=page_size,
                                cache_fmt="int8",
                                pool_pages=2 * args.slots * pps)
    # warm every engine's jit cache (all step shapes) so compile time is
    # excluded from the comparison; the prefix engine warms on a disjoint
    # prompt set so the measured runs start with a cold prefix index
    warm = reqs[: min(args.slots + 1, len(reqs))]
    warm_paged = build_workload(cfg, n_requests=len(warm),
                                short_len=short_len, long_len=long_len,
                                short_new=short_new, long_new=long_new,
                                seed=99)
    run_wave(wave_engine, warm)
    run_continuous(cont_engine, warm)
    run_continuous(paged_engine, warm)
    run_continuous(int8_engine, warm)
    run_continuous(prefix_engine, warm_paged)

    wave = run_wave(wave_engine, reqs)
    cont = run_continuous(cont_engine, reqs)
    paged = run_continuous(paged_engine, reqs)
    paged_int8 = run_continuous(int8_engine, reqs)
    # cold pass populates the prefix index, then the same prompts again:
    # the index hands their pages back and prefill steps disappear
    run_continuous(prefix_engine, reqs)
    paged_repeat = run_continuous(prefix_engine, reqs)
    # telemetry overhead row: the warmed continuous engine again, obs off
    # vs obs on (full optrace ring + span recording + metric publication);
    # best-of-3 per mode, since a smoke run's wall is tens of ms and a
    # single pass would measure scheduler noise, not instrumentation cost
    from repro.obs import optrace
    obs_off = max(
        (run_continuous(cont_engine, reqs)["tokens_per_s"]
         for _ in range(3)))
    optrace.enable()
    try:
        obs_on = max(
            (run_continuous(cont_engine, reqs)["tokens_per_s"]
             for _ in range(3)))
        spans_recorded = len(optrace.spans())
    finally:
        optrace.disable()
    obs_row = {
        "tokens_per_s_off": obs_off,
        "tokens_per_s_on": obs_on,
        "overhead_pct": round(100.0 * (1.0 - obs_on / obs_off), 2),
        "spans_recorded": spans_recorded,
    }
    # sampled telemetry row: production-rate mode (every 16th dispatch
    # into the ring, counters exact) must cost less than full tracing --
    # the budget is 2% vs the 5% full-tracing bound
    optrace.enable()
    optrace.configure(sample_every=16)
    try:
        obs_sampled = max(
            (run_continuous(cont_engine, reqs)["tokens_per_s"]
             for _ in range(3)))
        sampled_out = optrace.sampled_out_ops()
    finally:
        optrace.configure(sample_every=1)
        optrace.disable()
    obs_sampled_row = {
        "sample_every": 16,
        "tokens_per_s_off": obs_off,
        "tokens_per_s_on": obs_sampled,
        "overhead_pct": round(100.0 * (1.0 - obs_sampled / obs_off), 2),
        "sampled_out_ops": sampled_out,
    }
    # mesh row: same workload through a data-parallel mesh over every
    # visible device (model=1: CPU fake devices make TP all-reduces pure
    # overhead; the row exists to keep the sharded path measured and to
    # pin the token-identity guarantee, not to show CPU speedup)
    n_dev = jax.device_count()
    mesh_row = None
    if n_dev > 1:
        from repro.launch.mesh import make_debug_mesh
        mesh_engine = ServeEngine(params, cfg, batch_slots=args.slots,
                                  max_len=max_len,
                                  prefill_chunk=args.prefill_chunk,
                                  cache_dtype="float32",
                                  mesh=make_debug_mesh(n_dev, 1))
        run_continuous(mesh_engine, warm)
        mesh_row = run_continuous(mesh_engine, reqs)
        mesh_row["devices"] = n_dev
        mesh_row["mesh"] = {"data": n_dev, "model": 1}
        single = [list(map(int, o)) for o in cont_engine.generate(reqs)]
        meshed = [list(map(int, o)) for o in mesh_engine.generate(reqs)]
        mesh_row["tokens_match_single"] = single == meshed

    result = {
        "arch": cfg.name,
        "workload": {
            "requests": n_req, "slots": args.slots,
            "short": {"prompt": short_len, "max_new": short_new},
            "long": {"prompt": long_len, "max_new": long_new},
            "interleaved": True, "smoke": args.smoke,
        },
        "wave": wave,
        "continuous": cont,
        "paged": paged,
        "paged_int8": paged_int8,
        "paged_repeat": paged_repeat,
        "obs": obs_row,
        "obs_sampled": obs_sampled_row,
        **({"mesh_dp": mesh_row} if mesh_row is not None else {}),
        "speedup_tokens_per_s": round(
            cont["tokens_per_s"] / wave["tokens_per_s"], 3),
        "cache_reduction_int8_vs_dense_f32": round(
            cont["cache_bytes_per_slot"]
            / paged_int8["cache_bytes_per_slot"], 2),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))
    print(f"\nwrote {args.out}; continuous is "
          f"{result['speedup_tokens_per_s']:.2f}x wave tokens/s "
          f"(p99 latency {wave['p99_latency_s']:.2f}s -> "
          f"{cont['p99_latency_s']:.2f}s); int8 pages hold "
          f"{result['cache_reduction_int8_vs_dense_f32']:.1f}x less cache "
          f"per slot; repeat wave hit {paged_repeat.get('prefix_hits', 0)} "
          f"prefixes ({paged_repeat.get('prefix_hit_tokens', 0)} tokens); "
          f"obs overhead {obs_row['overhead_pct']:+.1f}% tokens/s "
          f"(sampled 1/16: {obs_sampled_row['overhead_pct']:+.1f}%)")
    if args.check_overhead:
        assert obs_row["overhead_pct"] <= 5.0, \
            f"full-tracing overhead {obs_row['overhead_pct']}% > 5% budget"
        assert obs_sampled_row["overhead_pct"] <= 2.0, \
            (f"sampled telemetry overhead {obs_sampled_row['overhead_pct']}%"
             " > 2% budget")


if __name__ == "__main__":
    main()
