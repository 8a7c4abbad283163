import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any other import (jax locks the device
count on first init).  For each cell this script:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod)
  2. constructs ShapeDtypeStruct stand-ins (no allocation) for the train /
     prefill / decode step's inputs, with NamedShardings from the framework's
     sharding rules
  3. ``jit(step).lower(...).compile()`` -- sharding mismatches, compile-time
     OOM and unsupported collectives all surface here
  4. records ``memory_analysis()`` (per-device bytes: proves it fits),
     ``cost_analysis()`` (per-device FLOPs/bytes) and the per-collective
     byte totals parsed from the optimized HLO -> JSON for §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out benchmarks/results/dryrun]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ModelConfig, ShapeCfg
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.memory_model import expected_device_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.parallel.specs import make_param_spec_fn
from repro.train.train_step import init_train_state, make_train_step

# ---------------------------------------------------------------------------
# cell policy
# ---------------------------------------------------------------------------

# pure full-attention archs skip long_500k (DESIGN.md §4); SSM/hybrid/SWA run.
LONG_OK = {"falcon-mamba-7b", "zamba2-7b", "mixtral-8x7b"}

# per-(arch, shape) training microbatch counts sized for 16 GiB/chip
MICROBATCH = {
    ("llama3-405b", "train_4k"): 16,
    ("deepseek-v3-671b", "train_4k"): 8,
    ("deepseek-coder-33b", "train_4k"): 4,
    ("qwen2.5-14b", "train_4k"): 2,
    ("yi-9b", "train_4k"): 2,
    ("mixtral-8x7b", "train_4k"): 4,
    ("zamba2-7b", "train_4k"): 2,
    ("falcon-mamba-7b", "train_4k"): 4,
}

# archs whose optimizer moments are kept in bf16 to fit 16 GiB/chip
BF16_OPT = {"llama3-405b", "deepseek-v3-671b"}


OVERRIDES: dict = {}   # hillclimb levers, set by --set key=value


def dryrun_config(arch: str) -> ModelConfig:
    cfg = get_config(arch)
    cfg = dataclasses.replace(
        cfg, param_dtype="bfloat16", compute_dtype="bfloat16", remat=True)
    if OVERRIDES:
        cfg = dataclasses.replace(cfg, **OVERRIDES)
    return cfg


def cells(multi_pod: bool) -> list[tuple[str, str]]:
    out = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            if shape == "long_500k" and arch not in LONG_OK:
                continue
            out.append((arch, shape))
    return out


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------


def batch_sharding(mesh, shape_dims):
    return shd.named_sharding(mesh, "batch", *([None] * (len(shape_dims) - 1)),
                              dims=shape_dims)


def input_specs(cfg: ModelConfig, shape: ShapeCfg, mesh, *,
                seq: int | None = None) -> dict:
    """ShapeDtypeStructs for the data batch of one step.

    ``seq`` overrides the token width (e.g. the continuous engine's chunked
    prefill step feeds C tokens/slot into a decode-shaped cell)."""
    B = shape.global_batch
    S = seq if seq is not None else (
        shape.seq_len if shape.kind != "decode" else 1)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=batch_sharding(mesh, dims))

    batch = {}
    if cfg.frontend == "audio":
        batch["embeds"] = sds((B, S, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            batch["tokens"] = sds((B, S), jnp.int32)
    else:
        batch["tokens"] = sds((B, S), jnp.int32)
    if cfg.frontend == "vlm" and shape.kind != "decode":
        batch["pixel_embeds"] = sds((B, cfg.n_patches, cfg.d_model),
                                    jnp.bfloat16)
    if shape.kind == "train":
        batch["labels"] = sds((B, S), jnp.int32)
        batch["mask"] = sds((B, S), jnp.float32)
    return batch


# ---------------------------------------------------------------------------
# cache sharding specs
# ---------------------------------------------------------------------------


# ``make_cache_spec_fn`` / ``tree_shardings`` moved to
# ``repro.parallel.sharding`` when the serve engines went mesh-parallel
# (the rules now cover paged pools too); re-exported here for callers of
# the dry-run module.
make_cache_spec_fn = shd.make_cache_spec_fn
tree_shardings = shd.tree_shardings


def opt_spec_fn(param_spec_fn):
    """Optimizer state mirrors the parameter sharding; step is replicated."""

    def fn(path, shape):
        names = [getattr(k, "key", None) for k in path]
        if "step" in names:
            return (None,) * len(shape)
        # strip the leading {'mu'|'nu'} key and delegate
        return param_spec_fn(path[1:], shape)

    return fn


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               serve_chunk: int = 0) -> dict:
    cfg = dryrun_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.flatten())
    spec_fn = make_param_spec_fn(cfg)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips, "kind": shape.kind,
    }
    if serve_chunk and shape.kind == "decode":
        # clamp to the smallest sliding window, as the engine does -- a
        # chunk wider than a rolling SWA cache is a shape production never
        # runs (its scatter would collide modulo the cache size)
        windows = [min(s.window, shape.seq_len)
                   for s in cfg.stages if s.window]
        serve_chunk = max(1, min([serve_chunk, *windows]))
        result["serve_chunk"] = serve_chunk
    t0 = time.time()

    with jax.set_mesh(mesh):
        params_shape = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg))
        params_shardings = shd.param_sharding(params_shape, mesh, spec_fn)
        params_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            params_shape, params_shardings)

        if shape.kind == "train":
            opt_cfg = adamw.OptConfig(
                state_dtype="bfloat16" if arch in BF16_OPT else "float32")
            micro = MICROBATCH.get((arch, shape_name), 1)
            accum = jnp.bfloat16 if arch in BF16_OPT else jnp.float32
            step_fn = make_train_step(cfg, opt_cfg, microbatches=micro,
                                      accum_dtype=accum)
            result["microbatches"] = micro

            state_shape = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg))
            state_shardings = {
                "params": params_shardings,
                "opt": tree_shardings(
                    state_shape["opt"], mesh, opt_spec_fn(spec_fn)),
                "step": shd.named_sharding(mesh, dims=()),
            }
            state_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                state_shape, state_shardings)
            batch_sds = input_specs(cfg, shape, mesh)
            result["expected_memory"] = expected_device_bytes(
                cfg, shape, mesh, state_sds=state_sds,
                params_sds=state_sds["params"], microbatches=micro)
            lowered = jax.jit(
                step_fn, donate_argnums=(0,),
                out_shardings=(state_shardings, None),
            ).lower(state_sds, batch_sds)

        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                hidden, _ = T.forward(params, batch, cfg)
                head = (params["embed"].T if cfg.tie_embeddings
                        else params["lm_head"])
                return jnp.einsum("bd,dv->bv", hidden[:, -1], head)

            batch_sds = input_specs(cfg, shape, mesh)
            result["expected_memory"] = expected_device_bytes(
                cfg, shape, mesh, params_sds=params_sds)
            lowered = jax.jit(prefill_step).lower(params_sds, batch_sds)

        else:  # decode
            caches_shape = jax.eval_shape(
                lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len,
                                      dtype=jnp.bfloat16))
            cache_shardings = tree_shardings(caches_shape, mesh,
                                             make_cache_spec_fn(mesh, cfg))
            caches_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                caches_shape, cache_shardings)
            result["expected_memory"] = expected_device_bytes(
                cfg, shape, mesh, params_sds=params_sds, cache_sds=caches_sds)
            if serve_chunk:
                # the continuous engine's chunked-prefill step: C teacher-
                # forced tokens per slot with a per-slot validity mask
                B, C = shape.global_batch, serve_chunk

                def chunk_step(params, caches, batch, valid):
                    logits, caches = T.prefill_step(params, caches, batch,
                                                    valid, cfg)
                    return jnp.argmax(logits[:, -1], axis=-1), caches

                batch_sds = input_specs(cfg, shape, mesh, seq=C)
                valid_sds = jax.ShapeDtypeStruct(
                    (B, C), jnp.bool_,
                    sharding=batch_sharding(mesh, (B, C)))
                lowered = jax.jit(
                    chunk_step, donate_argnums=(1,),
                    out_shardings=(None, cache_shardings),
                ).lower(params_sds, caches_sds, batch_sds, valid_sds)
            else:
                def serve_step(params, caches, batch):
                    logits, caches = T.decode_step(params, caches, batch, cfg)
                    return jnp.argmax(logits[:, -1], axis=-1), caches

                batch_sds = input_specs(cfg, shape, mesh)
                lowered = jax.jit(
                    serve_step, donate_argnums=(1,),
                    out_shardings=(None, cache_shardings),
                ).lower(params_sds, caches_sds, batch_sds)

        result["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)

        ma = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        result["memory"]["live_bytes_per_device"] = int(live)
        result["memory"]["fits_16GiB"] = bool(live < 16 * 1024**3)

        hlo_text = compiled.as_text()
        # XLA:CPU has no native bf16 dots: it inserts fp32 converts of the
        # bf16 operands (weights/caches), inflating temp vs a real TPU
        # compile where the MXU consumes bf16 directly.  Quantify those
        # converts so the table can report a TPU-adjusted estimate.
        upcast = _cpu_upcast_bytes(hlo_text)
        result["memory"]["cpu_upcast_f32_bytes"] = upcast
        adj = max(0, live - upcast)
        result["memory"]["live_bytes_tpu_adjusted"] = int(adj)
        result["memory"]["fits_16GiB_tpu_adjusted"] = bool(adj < 16 * 1024**3)

        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):       # jax < 0.5: one dict/device
            ca = ca[0] if ca else {}
        result["cost"] = {
            # NOTE: XLA counts while bodies once -- see 'corrected' below.
            "flops_per_device": float(ca.get("flops", -1)),
            "bytes_per_device": float(ca.get("bytes accessed", -1)),
        }
        result["collectives"] = collective_bytes(hlo_text)
        # loop-corrected walker (trip-count multipliers; dots + collectives)
        hc = analyze_hlo(hlo_text)
        result["corrected"] = {
            "dot_flops_per_device": hc.dot_flops,
            "dot_bytes_per_device": hc.dot_bytes,
            "collective_bytes_per_device": hc.collective_bytes,
            "collective_by_kind": hc.collective_bytes_by_kind,
            "while_loops": hc.while_loops,
        }
    return result


_CONVERT_RE = re.compile(
    r"%[\w.\-]+ = f32\[([\d,]+)\][^=]*? convert\(%([\w.\-]+)\)")


def _cpu_upcast_bytes(hlo_text: str, min_bytes: int = 16 * 1024**2) -> int:
    """Estimated bytes of bf16->f32 convert results >= min_bytes (the
    XLA:CPU bf16-dot-upcast artifact; ~0 on a TPU compile)."""
    shapes: dict[str, str] = {}
    for m in _DEF_RE.finditer(hlo_text):
        name, dt, _ = m.groups()
        shapes[name] = dt
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        dims, operand = m.groups()
        if shapes.get(operand) != "bf16":
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n * 4 >= min_bytes:
            total += n * 4
    return int(total)


_DEF_RE = re.compile(r"%([\w.\-]+) = (\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective in the optimized HLO."""
    shapes: dict[str, tuple[str, int]] = {}
    for m in _DEF_RE.finditer(hlo_text):
        name, dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        shapes[name] = (dt, n)

    totals = {k: 0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        mm = re.search(r"%([\w.\-]+) = (\w+)\[([\d,]*)\][^=]*? "
                       r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                       r"collective-permute)(?:-start)?\(([^)]*)\)", line)
        if not mm:
            continue
        _, res_dt, res_dims, kind, operands = mm.groups()
        done = False
        for op in operands.split(","):
            op = op.strip().lstrip("%")
            if op in shapes:
                dt, n = shapes[op]
                totals[kind] += n * _DTYPE_BYTES.get(dt, 4)
                done = True
        if not done:
            n = 1
            for d in res_dims.split(","):
                if d:
                    n *= int(d)
            totals[kind] += n * _DTYPE_BYTES.get(res_dt, 4)
        counts[kind] += 1
    totals = {k: int(v) for k, v in totals.items()}
    return {"bytes": totals, "counts": counts,
            "total_bytes": int(sum(totals.values()))}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--serve-chunk", type=int, default=0,
                    help="decode cells: lower the continuous engine's "
                         "chunked prefill step (C tokens/slot) instead of "
                         "the one-token decode step")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="config override, e.g. --set seq_shard=False "
                         "--set exact_causal=True (hillclimb levers)")
    ap.add_argument("--trace-out", default=None,
                    help="enable repro.obs and write per-cell lower/compile "
                         "spans as a Chrome-trace JSON")
    ap.add_argument("--metrics-out", default=None,
                    help="enable repro.obs and write a metrics snapshot")
    ap.add_argument("--stream-dir", default=None,
                    help="stream periodic metric snapshots while a full "
                         "--all sweep lowers (long runs: watch progress "
                         "from another terminal)")
    ap.add_argument("--stream-interval", type=float, default=None)
    args = ap.parse_args()

    if args.trace_out or args.metrics_out or args.stream_dir:
        import repro.obs as obs
        from repro.obs import streaming
        obs.enable()
        if args.stream_dir:
            streaming.start(args.stream_dir,
                            interval_s=args.stream_interval
                            if args.stream_interval is not None
                            else streaming.DEFAULT_INTERVAL_S)

    for kv in args.set:
        key, val = kv.split("=", 1)
        OVERRIDES[key] = {"True": True, "False": False}.get(val) \
            if val in ("True", "False") else (
                int(val) if val.lstrip("-").isdigit() else val)

    os.makedirs(args.out, exist_ok=True)
    todo = (cells(args.multi_pod) if args.all
            else [(args.arch, args.shape)])
    failures = 0
    for arch, shape in todo:
        tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
        if args.serve_chunk and SHAPES[shape].kind == "decode":
            tag += f"_chunk{args.serve_chunk}"
        path = os.path.join(args.out, tag + ".json")
        try:
            from repro.obs import optrace
            with optrace.span(f"lower_cell:{tag}", cat="launch",
                              arch=arch, shape=shape):
                res = lower_cell(arch, shape, multi_pod=args.multi_pod,
                                 serve_chunk=args.serve_chunk)
            print(f"[ok] {tag}: compile={res['compile_s']}s "
                  f"live={res['memory']['live_bytes_per_device']/2**30:.2f}GiB "
                  f"coll={res['collectives']['total_bytes']/2**20:.1f}MiB")
        except Exception as e:  # noqa: BLE001
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            failures += 1
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
    if args.trace_out or args.metrics_out or args.stream_dir:
        import repro.obs as obs
        if args.stream_dir:
            from repro.obs import streaming
            streaming.stop()
            print(f"streamed snapshots in {args.stream_dir}")
        if args.trace_out:
            obs.write_chrome_trace(args.trace_out, process_name="dryrun")
            print(f"trace written to {args.trace_out}")
        if args.metrics_out:
            obs.REGISTRY.write_json(args.metrics_out)
            print(f"metrics written to {args.metrics_out}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
