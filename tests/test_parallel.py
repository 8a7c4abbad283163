"""Mesh-parallel serving: sharding rules and token-identity regressions.

The load-bearing property is BIT identity: an engine sharded over a mesh
must produce exactly the tokens the single-device engine produces, for
every cache layout (dense float, paged int8, int8 flash decode) and for
the decoupled prefill->insert->generate path.  The multi-device cases run
under 8 fake CPU devices (the CI ``mesh`` shard sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and are skipped
when fewer devices are visible, so the tier-1 shards still execute the
single-device rows: sharding-rule units, off-mesh no-ops, mesh(1,1)
identity, and decoupled-vs-inline identity.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_vision_config
from repro.launch.mesh import make_debug_mesh, parse_mesh
from repro.models import transformer as T
from repro.parallel import sharding as shd
from repro.serve.engine import Request, ServeEngine
from repro.vision import models as vmodels
from repro.vision.engine import ImageRequest, VisionEngine

KEY = jax.random.PRNGKey(0)

multi = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI mesh shard) before jax initialises")


def _stub_mesh(**shape):
    """Shape-only stand-in for the pure spec functions (resolve,
    make_cache_spec_fn) -- they read only axis_names and shape, so rules
    for meshes far larger than the test host stay unit-testable."""
    return types.SimpleNamespace(axis_names=tuple(shape), shape=shape)


def _cfg(arch):
    cfg = get_config(arch, reduced=True)
    # lift MoE capacity so chunked prefill and decode route identically
    return dataclasses.replace(cfg, capacity_factor=64.0)


def _engine(params, cfg, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("seed", 0)
    return ServeEngine(params, cfg, **kw)


def _tokens(params, cfg, reqs, **kw):
    return [list(map(int, o)) for o in _engine(params, cfg, **kw)
            .generate(reqs)]


def _requests(cfg, lens, max_new=5, seed=1):
    key = jax.random.PRNGKey(seed)
    reqs = []
    for plen in lens:
        key, sub = jax.random.split(key)
        prompt = [int(t) for t in jax.random.randint(sub, (plen,), 2,
                                                     cfg.vocab)]
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new,
                            eos_id=-1))
    return reqs


class TestShardingRules:
    def test_resolve_drops_nondivisible_axis(self):
        mesh = _stub_mesh(data=2, model=16)
        # 24 heads don't divide model=16 -> replicated; 32 do -> sharded
        assert shd.resolve(mesh, (None, "model", None), (4, 24, 64)) \
            == jax.sharding.PartitionSpec(None, None, None)
        assert shd.resolve(mesh, (None, "model", None), (4, 32, 64)) \
            == jax.sharding.PartitionSpec(None, "model", None)

    def test_resolve_drops_unknown_axis_names(self):
        mesh = _stub_mesh(data=2, model=4)
        assert shd.resolve(mesh, (("pod", "data"), None), (8, 8)) \
            == jax.sharding.PartitionSpec("data", None)

    def test_fsdp_expansion_single_and_multi_pod(self):
        assert shd.fsdp_axes(_stub_mesh(data=4, model=4)) == ("data",)
        assert shd.fsdp_axes(
            _stub_mesh(pod=2, data=4, model=4)) == ("pod", "data")
        assert shd.batch_axes(_stub_mesh(data=4, model=4)) == ("data",)

    def test_resolve_composite_fsdp_batch_divisibility(self):
        mesh = _stub_mesh(pod=2, data=4, model=4)
        # batch 8 divides pod*data=8 -> composite entry survives whole
        assert shd.resolve(mesh, (("pod", "data"), None), (8, 16)) \
            == jax.sharding.PartitionSpec(("pod", "data"), None)
        # batch 4 does not divide 8 -> replicated
        assert shd.resolve(mesh, (("pod", "data"), None), (4, 16)) \
            == jax.sharding.PartitionSpec(None, None)

    def test_current_mesh_none_off_mesh(self):
        assert shd.current_mesh() is None

    def test_current_mesh_inside_context(self):
        mesh = make_debug_mesh(1, 1)
        with jax.set_mesh(mesh):
            got = shd.current_mesh()
            assert got is not None
            assert dict(got.shape) == {"data": 1, "model": 1}
        assert shd.current_mesh() is None

    def test_constrain_noop_off_mesh(self):
        x = jnp.arange(6.0).reshape(2, 3)
        assert shd.constrain(x, "batch", "model") is x
        assert shd.constrain_priority(x, 1, [1]) is x

    def test_make_debug_mesh_requires_devices(self):
        need = 4 * jax.device_count()
        with pytest.raises(ValueError, match="devices"):
            make_debug_mesh(need, 1)

    def test_parse_mesh(self):
        assert parse_mesh(None) is None
        assert parse_mesh("") is None
        assert dict(parse_mesh("1x1").shape) == {"data": 1, "model": 1}
        with pytest.raises(ValueError, match="DATAxMODEL"):
            parse_mesh("2x2x2")


class TestCacheSpecRules:
    def _entries(self, name, shape, *, layered=False, **mesh_shape):
        mesh_shape = mesh_shape or {"data": 2, "model": 4}
        fn = shd.make_cache_spec_fn(_stub_mesh(**mesh_shape))
        path = [jax.tree_util.DictKey("layers")] if layered else []
        path.append(jax.tree_util.DictKey(name))
        return fn(tuple(path), shape)

    def test_dense_kv_shards_kv_heads(self):
        got = self._entries("k", (4, 2, 16, 8, 64), layered=True)
        assert got == (None, "batch", None, "model", None)

    def test_dense_kv_falls_back_to_sequence(self):
        # 6 kv-heads don't divide model=4, seq 16 does
        got = self._entries("k", (4, 2, 16, 6, 64), layered=True)
        assert got == (None, "batch", "model", None, None)

    def test_paged_pool_shards_kv_head_axis(self):
        got = self._entries("k_pages", (4, 32, 4, 8, 64), layered=True)
        assert got == (None, None, None, "model", None)

    def test_paged_pool_replicates_nondivisible_heads(self):
        got = self._entries("k_pages", (4, 32, 4, 6, 64), layered=True)
        assert got == (None, None, None, None, None)

    def test_scale_pool_mirrors_payload(self):
        got = self._entries("k_scales", (4, 32, 4, 8), layered=True)
        assert got == (None, None, None, "model")

    def test_page_table_always_replicated(self):
        got = self._entries("page_table", (8, 16))
        assert got == (None, None)

    def test_slot_counters_follow_batch(self):
        assert self._entries("len", (8,)) == ("batch",)


class TestMeshIdentitySingleDevice:
    """Tier-1 rows: run on one device, prove mesh(1,1) and the decoupled
    prefill path change nothing."""

    def test_mesh_1x1_token_identity(self):
        cfg = _cfg("yi-9b")
        params = T.init_params(KEY, cfg)
        reqs = _requests(cfg, [5, 3])
        base = _tokens(params, cfg, reqs)
        meshed = _tokens(params, cfg, reqs, mesh=make_debug_mesh(1, 1))
        assert meshed == base

    def test_decoupled_prefill_matches_inline(self):
        cfg = _cfg("yi-9b")
        params = T.init_params(KEY, cfg)
        reqs = _requests(cfg, [5, 3, 7])
        inline = _tokens(params, cfg, reqs)
        dec = _tokens(params, cfg, reqs, decouple_prefill=True)
        assert dec == inline

    def test_decoupled_prefill_reports_stats(self):
        cfg = _cfg("yi-9b")
        params = T.init_params(KEY, cfg)
        eng = _engine(params, cfg, decouple_prefill=True)
        eng.generate(_requests(cfg, [5, 3]))
        assert eng.last_stats["decoupled_prefill_tokens"] == 8
        assert eng.declared_step_widths() == (1,)
        assert eng.declared_prefill_widths() == (eng.prefill_chunk,)

    def test_paged_decouple_rejected(self):
        cfg = _cfg("yi-9b")
        params = T.init_params(KEY, cfg)
        with pytest.raises(ValueError, match="decouple"):
            _engine(params, cfg, paged=True, page_size=4,
                    decouple_prefill=True)


@multi
class TestMeshIdentityMultiDevice:
    """The 8-fake-device rows: mesh(2,4) = DP x TP must be bit-identical
    to the un-meshed engine for every cache layout."""

    def _check(self, arch, **kw):
        cfg = _cfg(arch)
        params = T.init_params(KEY, cfg)
        reqs = _requests(cfg, [5, 3, 7], max_new=4)
        base = _tokens(params, cfg, reqs, **kw)
        meshed = _tokens(params, cfg, reqs, mesh=make_debug_mesh(2, 4),
                         **kw)
        assert meshed == base

    def test_float(self):
        self._check("yi-9b")

    def test_paged_int8(self):
        self._check("yi-9b", paged=True, page_size=4, cache_fmt="int8")

    def test_attn_int8(self):
        self._check("yi-9b", attn_int8=True)

    def test_decoupled_prefill(self):
        self._check("yi-9b", decouple_prefill=True)

    def test_decoupled_swa_moe(self):
        self._check("mixtral-8x7b", decouple_prefill=True)

    def test_decoupled_mla(self):
        self._check("deepseek-v3-671b", decouple_prefill=True)

    def test_mesh_stats_row(self):
        cfg = _cfg("yi-9b")
        params = T.init_params(KEY, cfg)
        eng = _engine(params, cfg, mesh=make_debug_mesh(2, 4))
        eng.generate(_requests(cfg, [5]))
        assert eng.last_stats["mesh"] == {
            "devices": 8, "axes": {"data": 2, "model": 4}}


@multi
class TestVisionMeshIdentity:
    def test_data_parallel_identity(self):
        cfg = get_vision_config("resnet50", reduced=True)
        params = vmodels.init(KEY, cfg)
        rng = np.random.default_rng(0)
        reqs = [ImageRequest(image=rng.standard_normal(
                    (*cfg.input_hw, cfg.in_channels)).astype(np.float32))
                for _ in range(5)]

        def run(mesh=None):
            eng = VisionEngine(params, cfg, batch_slots=4, mesh=mesh)
            eng.warmup()
            return eng.infer(reqs), eng.last_stats

        base, _ = run()
        meshed, st = run(make_debug_mesh(8, 1))
        for a, b in zip(base, meshed):
            jax.tree.map(np.testing.assert_array_equal, a, b)
        assert st["mesh"] == {"devices": 8,
                              "axes": {"data": 8, "model": 1}}
