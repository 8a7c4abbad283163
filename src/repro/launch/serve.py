"""Serving launcher: continuous-batching (or wave-batched) generation demo."""
from __future__ import annotations

import argparse
import os
import sys


def _apply_fake_devices(argv) -> None:
    """``--fake-devices N`` must take effect before jax initialises its
    backend (XLA reads the flag exactly once), so it is applied here at
    import time from the raw argv, ahead of the ``import jax`` below."""
    for i, a in enumerate(argv):
        if a == "--fake-devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--fake-devices="):
            n = a.split("=", 1)[1]
        else:
            continue
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(n)}")
        return


_apply_fake_devices(sys.argv)

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serve.engine import (
    QUEUE_POLICIES,
    Request,
    ServeEngine,
    WaveServeEngine,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="yi-9b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (CPU-sized; the "
                         "default); --no-reduced serves published widths")
    ap.add_argument("--engine", choices=("continuous", "wave"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--mixed", action="store_true",
                    help="alternate short/3x-long prompts (shows the "
                         "head-of-line win of continuous batching)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--queue-policy", choices=QUEUE_POLICIES, default="fifo")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="shard the engine over a device mesh, e.g. 2x4 "
                         "(data=2, model=4); requires data*model <= "
                         "jax.device_count()")
    ap.add_argument("--decouple-prefill", action="store_true",
                    help="run prompts through a dedicated prefill step and "
                         "hand the cache to a decode slot via a jitted "
                         "insert (dense caches only)")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="fake N host devices (XLA "
                         "--xla_force_host_platform_device_count; applied "
                         "before jax backend init) for trying --mesh on CPU")
    ap.add_argument("--trace-out", default=None,
                    help="enable repro.obs and write a Chrome-trace JSON "
                         "(load at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None,
                    help="enable repro.obs and write a metrics snapshot")
    ap.add_argument("--prom-out", default=None,
                    help="enable repro.obs and write the Prometheus text "
                         "exposition")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace into this "
                         "directory (named scopes nest under serve steps)")
    ap.add_argument("--sample-every", type=int, default=1,
                    help="op-ring sampling stride (counters stay exact)")
    ap.add_argument("--stream-dir", default=None,
                    help="stream periodic metric snapshots (JSONL + prom "
                         "textfile) into this directory during the serve")
    ap.add_argument("--stream-interval", type=float, default=None,
                    help="seconds between streaming snapshots")
    args = ap.parse_args()
    use_compile_cache()

    obs_on = bool(args.trace_out or args.metrics_out or args.prom_out
                  or args.stream_dir or args.profile_dir
                  or args.sample_every > 1)
    if obs_on:
        import repro.obs as obs
        from repro.obs import profiler, streaming
        obs.enable()
        obs.configure(sample_every=args.sample_every)
        if args.profile_dir:
            profiler.start(args.profile_dir)
        if args.stream_dir:
            interval = args.stream_interval \
                if args.stream_interval is not None \
                else streaming.DEFAULT_INTERVAL_S
            streaming.start(args.stream_dir, interval_s=interval)

    from repro.launch.mesh import parse_mesh
    mesh = parse_mesh(args.mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {mesh.size} of "
              f"{jax.device_count()} device(s)")

    cfg = get_config(args.arch, reduced=args.reduced)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    slots = min(args.batch_slots, args.requests)
    max_prompt = args.prompt_len * (3 if args.mixed else 1)
    max_len = max_prompt + args.max_new + 1
    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        plen = (args.prompt_len * (3 if args.mixed and i % 2 else 1))
        prompt = jax.random.randint(sub, (plen,), 2, cfg.vocab)
        reqs.append(Request(prompt=[int(t) for t in prompt],
                            max_new_tokens=args.max_new))

    if args.engine == "wave":
        engine = WaveServeEngine(params, cfg, batch_slots=slots,
                                 max_len=max_len,
                                 temperature=args.temperature)
    else:
        engine = ServeEngine(params, cfg, batch_slots=slots, max_len=max_len,
                             prefill_chunk=args.prefill_chunk,
                             queue_policy=args.queue_policy,
                             temperature=args.temperature, mesh=mesh,
                             decouple_prefill=args.decouple_prefill)
    outs = engine.generate(reqs)
    for i, o in enumerate(outs):
        print(f"req{i} ({len(reqs[i].prompt)}-token prompt): {o}")
    stats = getattr(engine, "last_stats", None)
    if stats:
        lat = [r["latency_s"] for r in stats["requests"]]
        print(f"{stats['generated_tokens']} tokens in "
              f"{stats['wall_s']:.2f}s ({stats['tokens_per_s']:.1f} tok/s, "
              f"{stats['steps']} steps, p50 latency {np.percentile(lat, 50):.2f}s, "
              f"p99 {np.percentile(lat, 99):.2f}s)")

    if obs_on:
        import repro.obs as obs
        from repro.obs import profiler, streaming
        if args.profile_dir and profiler.stop():
            print(f"profiler trace in {args.profile_dir}")
        if args.stream_dir:
            streaming.stop()
            print(f"streamed snapshots in {args.stream_dir}")
        if args.trace_out:
            obs.write_chrome_trace(args.trace_out, process_name="serve")
            print(f"trace written to {args.trace_out}")
        if args.metrics_out:
            obs.REGISTRY.write_json(args.metrics_out)
            print(f"metrics written to {args.metrics_out}")
        if args.prom_out:
            with open(args.prom_out, "w") as f:
                f.write(obs.metrics.prometheus_text())
            print(f"prometheus exposition written to {args.prom_out}")


if __name__ == "__main__":
    main()
