"""Paged, quantized KV cache: device primitives, allocator, engine identity.

The load-bearing properties, in order of blast radius:

  1. a paged float engine is TOKEN-IDENTICAL to the dense engine under
     greedy decoding (GQA, SWA, MLA) -- paging is pure data movement;
  2. int8 page payloads (per-token-per-head scales, dequant-on-read) stay
     token-identical on the same archetypes at reduced test scale, up to
     a top-2 near-tie in the dense run;
  3. prefix reuse skips prefill steps without changing a single token, and
     the int8-paged cache footprint lands >= 3x below dense f32;
  4. the host allocator's machine-checkable contract
     (``PagePool.invariant_errors``) actually detects seeded corruption --
     a checker that can't see planted bugs guards nothing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import kvcache as KV
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.PRNGKey(0)

# GQA / SWA / MLA -- the three attention archetypes whose cache layouts
# differ (dense KV heads, rolling window, compressed latent + rope key)
ARCHS = ["yi-9b", "mixtral-8x7b", "deepseek-v3-671b"]


def _cfg(arch):
    cfg = get_config(arch, reduced=True)
    return dataclasses.replace(cfg, capacity_factor=64.0)


def _params(cfg):
    return T.init_params(KEY, cfg)


def _requests(cfg, specs, seed=1):
    key = jax.random.PRNGKey(seed)
    reqs = []
    for plen, mnew, eos in specs:
        key, sub = jax.random.split(key)
        prompt = [int(t) for t in jax.random.randint(sub, (plen,), 2,
                                                     cfg.vocab)]
        reqs.append(Request(prompt=prompt, max_new_tokens=mnew, eos_id=eos))
    return reqs


MIXED = [(3, 6, 1), (9, 4, 7), (5, 8, 1), (12, 3, 2), (2, 5, 1), (7, 7, 3)]


# ---------------------------------------------------------------------------
# device primitives
# ---------------------------------------------------------------------------


class TestQuantizeTokens:
    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_round_trip_error_bounded(self, fmt):
        x = jax.random.normal(KEY, (3, 5, 2, 8), jnp.float32)
        payload, scale = KV.quantize_tokens(x, fmt)
        assert scale.shape == x.shape[:-1]          # one scale per token-head
        back = KV.dequantize_tokens(payload, scale, jnp.float32)
        amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
        err = np.abs(np.asarray(back) - np.asarray(x))
        # int8: half an lsb of the per-row grid; fp8 e4m3: ~2^-3 relative
        bound = amax / 254 if fmt == "int8" else amax * 0.0725
        assert (err <= bound + 1e-7).all()

    def test_zero_rows_survive(self):
        x = jnp.zeros((2, 4, 8), jnp.float32)
        payload, scale = KV.quantize_tokens(x, "int8")
        assert np.isfinite(np.asarray(scale)).all()
        assert not np.asarray(
            KV.dequantize_tokens(payload, scale, jnp.float32)).any()


class TestPageMoves:
    def test_scatter_then_gather_round_trips(self):
        ps, n, P = 4, 3, 7
        pool = jnp.zeros((P, ps, 2, 5), jnp.float32)
        table = jnp.asarray([[6, 2, 4], [1, 0, 3]])     # 2 slots, 3 pages
        vals = jax.random.normal(KEY, (2, 6, 2, 5), jnp.float32)
        idx = jnp.broadcast_to(jnp.arange(2, 8), (2, 6))  # tokens 2..7
        pool = KV.scatter_pages(pool, table, vals, idx, jnp.ones((2, 6), bool))
        seq = KV.gather_pages(pool, table)              # (2, 12, 2, 5)
        np.testing.assert_array_equal(np.asarray(seq[:, 2:8]),
                                      np.asarray(vals))
        assert not np.asarray(seq[:, :2]).any()         # untouched rows zero
        assert not np.asarray(seq[:, 8:]).any()

    def test_invalid_lanes_never_write(self):
        ps = 4
        pool = jnp.zeros((3, ps, 2), jnp.float32)
        table = jnp.asarray([[0, 1, 2]])
        vals = jnp.ones((1, 2, 2))
        idx = jnp.asarray([[1, 5]])
        out = KV.scatter_pages(pool, table, vals, idx,
                               jnp.asarray([[True, False]]))
        assert np.asarray(out[0, 1]).all()              # valid token landed
        assert not np.asarray(out[1]).any()             # masked token dropped

    def test_read_seq_dequantizes(self):
        pcfg = KV.PagedCacheConfig(page_size=4, pages_per_slot=2,
                                   pool_pages=4, fmt="int8")
        cache = KV.init_paged_seq_cache({"k": (2, 8)}, batch=1, pcfg=pcfg)
        table = jnp.asarray([[2, 0]])
        vals = jax.random.normal(KEY, (1, 3, 2, 8), jnp.float32)
        idx = jnp.arange(3)[None, :]
        cache.update(KV.write_seq(cache, "k", table, vals, idx,
                                  jnp.ones((1, 3), bool), "int8"))
        seq = KV.read_seq(cache, "k", table, 2, dtype=jnp.float32)
        assert seq.shape == (1, 8, 2, 8)
        np.testing.assert_allclose(np.asarray(seq[:, :3]), np.asarray(vals),
                                   atol=0.02, rtol=0.02)


# ---------------------------------------------------------------------------
# host allocator
# ---------------------------------------------------------------------------


class TestPagePool:
    def test_admit_release_round_trip(self):
        pool = KV.PagePool(8, 4)
        pages, shared = pool.admit(0, (1, 2, 3, 4, 5), 9, prefix=False)
        assert shared == 0 and len(pages) == 3          # ceil(9/4)
        assert pool.free_pages == 5
        assert pool.invariant_errors() == []
        pool.release(0)
        assert pool.free_pages == 8
        assert (pool.refcount == 0).all()

    def test_prefix_match_capped_below_full_prompt(self):
        # the last prompt token must be re-fed (the finishing prefill step
        # needs logits), so a prompt of exactly k full pages shares k-1
        pool = KV.PagePool(16, 4)
        prompt = tuple(range(100, 108))                 # exactly 2 pages
        pages, _ = pool.admit(0, prompt, 10)
        pool.release(0, prompt=prompt)
        pages2, shared = pool.admit(1, prompt, 10)
        assert shared == 4                              # one page, not two
        assert pages2[0] == pages[0]                    # the registered page
        assert pool.invariant_errors() == []

    def test_shared_pages_are_frozen_fresh_are_writable(self):
        pool = KV.PagePool(16, 4)
        prompt = tuple(range(12))
        pool.admit(0, prompt, 14)
        pool.release(0, prompt=prompt)
        pool.admit(1, prompt, 14)
        pool.admit(2, prompt, 14)                       # concurrent sharer
        assert pool.invariant_errors() == []            # no writable aliasing
        assert pool.slot_pages(1)[0] == pool.slot_pages(2)[0]
        assert pool.slot_pages(1)[-1] != pool.slot_pages(2)[-1]

    def test_eviction_under_pressure(self):
        pool = KV.PagePool(4, 4)
        for i in range(3):
            prompt = tuple(range(i * 10, i * 10 + 4))
            pool.admit(0, prompt, 5)
            pool.release(0, prompt=prompt)
        # free pages < demand: LRU prefix entries must make room
        pool.admit(1, (77, 78, 79), 4 * 4)
        assert pool.evictions > 0
        assert pool.invariant_errors() == []

    def test_exhaustion_raises_and_rolls_back(self):
        pool = KV.PagePool(4, 4)
        pool.admit(0, (1, 2), 16)
        rc = pool.refcount.copy()
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.admit(1, (3, 4), 4)
        np.testing.assert_array_equal(pool.refcount, rc)
        assert pool.invariant_errors() == []

    def test_double_admit_same_slot_rejected(self):
        pool = KV.PagePool(8, 4)
        pool.admit(0, (1, 2), 4)
        with pytest.raises(ValueError, match="already holds"):
            pool.admit(0, (3, 4), 4)


class TestInvariantChecker:
    """The contract checker must DETECT planted corruption, not just pass."""

    def _live_pool(self):
        pool = KV.PagePool(8, 4)
        pool.admit(0, (1, 2, 3, 4, 5), 8, prefix=False)
        assert pool.invariant_errors() == []
        return pool

    def test_detects_refcount_drift(self):
        pool = self._live_pool()
        pool.refcount[pool.slot_pages(0)[0]] += 1
        assert "PGT003" in {c for c, _ in pool.invariant_errors()}

    def test_detects_free_but_referenced(self):
        pool = self._live_pool()
        pool._free.appendleft(pool.slot_pages(0)[0])
        assert "PGT002" in {c for c, _ in pool.invariant_errors()}

    def test_detects_leaked_page(self):
        pool = self._live_pool()
        pool._free.pop()                                # vanish a free page
        assert "PGT004" in {c for c, _ in pool.invariant_errors()}

    def test_detects_writable_aliasing(self):
        pool = self._live_pool()
        # alias slot 0's writable page into a second slot's writable region
        pool._slot_pages[1] = [pool.slot_pages(0)[-1]]
        pool._slot_shared[1] = 0
        assert "PGT001" in {c for c, _ in pool.invariant_errors()}

    def test_detects_writable_while_frozen(self):
        pool = KV.PagePool(8, 4)
        prompt = tuple(range(8))
        pool.admit(0, prompt, 10)
        pool.release(0, prompt=prompt)
        pool.admit(1, prompt, 10)
        # corrupt the share accounting: claim slot 1 shares nothing, making
        # the frozen prefix page look writable
        pool._slot_shared[1] = 0
        assert "PGT001" in {c for c, _ in pool.invariant_errors()}


# ---------------------------------------------------------------------------
# engine identity: paged == dense, quantized pages, prefix reuse, memory
# ---------------------------------------------------------------------------


class TestPagedEngineIdentity:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_paged_float_matches_dense(self, arch):
        cfg = _cfg(arch)
        params = _params(cfg)
        reqs = _requests(cfg, MIXED)
        dense = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                            prefill_chunk=4).generate(reqs)
        paged = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                            prefill_chunk=4, paged=True,
                            page_size=4).generate(reqs)
        assert paged == dense

    @pytest.mark.parametrize("arch", ARCHS)
    def test_paged_int8_matches_dense(self, arch):
        """Quantize-on-write pages keep greedy decoding token-identical at
        reduced test scale wherever the dense run's top-2 logit margin
        exceeds the per-token int8 rounding step (1/127 of the logit
        scale).  A request may part ways only at such a near-tie, and must
        match token for token up to it."""
        cfg = _cfg(arch)
        params = _params(cfg)
        reqs = _requests(cfg, MIXED)
        dense = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                            prefill_chunk=4).generate(reqs)
        q = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                        prefill_chunk=4, paged=True, page_size=4,
                        cache_fmt="int8").generate(reqs)
        assert [len(t) for t in q] == [len(t) for t in dense]
        for req, d_toks, q_toks in zip(reqs, dense, q):
            if q_toks == d_toks:
                continue
            j = next(i for i, (a, b) in enumerate(zip(d_toks, q_toks))
                     if a != b)
            seq = jnp.asarray([req.prompt + d_toks[:j]])
            hidden, _ = T.forward(params, {"tokens": seq}, cfg)
            logits = np.asarray(T._head_logits(params, hidden[:, -1:], cfg))
            top2 = np.sort(logits.ravel())[-2:]
            assert top2[1] - top2[0] < np.abs(logits).max() / 127.0, (
                f"int8 pages flipped token {j} at a top-2 margin of "
                f"{top2[1] - top2[0]:.4g}, not a near-tie")

    def test_fp8_pages_serve(self):
        cfg = _cfg("yi-9b")
        params = _params(cfg)
        reqs = _requests(cfg, [(5, 4, 1), (8, 4, 1)])
        outs = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                           paged=True, page_size=4,
                           cache_fmt="fp8").generate(reqs)
        assert all(1 <= len(o) <= 4 for o in outs)

    def test_cache_fmt_requires_paged(self):
        cfg = _cfg("yi-9b")
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(_params(cfg), cfg, cache_fmt="int8")

    def test_pool_pressure_requeues_not_crashes(self):
        # pool holds pages for ~1.5 requests: admissions must serialize
        cfg = _cfg("yi-9b")
        params = _params(cfg)
        reqs = _requests(cfg, [(6, 4, 1)] * 4)
        eng = ServeEngine(params, cfg, batch_slots=4, max_len=32, paged=True,
                          page_size=4, pool_pages=5, prefix_cache=False)
        outs = eng.generate(reqs)
        solo = ServeEngine(params, cfg, batch_slots=1,
                           max_len=32).generate(reqs)
        assert outs == solo
        assert eng.pool.invariant_errors() == []

    def test_oversized_request_fails_fast_when_pool_idle(self):
        cfg = _cfg("yi-9b")
        eng = ServeEngine(_params(cfg), cfg, batch_slots=2, max_len=32,
                          paged=True, page_size=4, pool_pages=2)
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.generate(_requests(cfg, [(6, 4, 1)]))


class TestPrefixReuse:
    def test_repeat_prompt_skips_prefill_steps(self):
        cfg = _cfg("yi-9b")
        params = _params(cfg)
        reqs = _requests(cfg, [(9, 4, 7), (9, 4, 7)])
        reqs[1].prompt = list(reqs[0].prompt)           # identical prompt
        eng = ServeEngine(params, cfg, batch_slots=1, max_len=32,
                          prefill_chunk=2, paged=True, page_size=4)
        cold = eng.generate([reqs[0]])
        steps_cold = eng.last_stats["steps"]
        warm = eng.generate([reqs[1]])
        steps_warm = eng.last_stats["steps"]
        assert warm == cold                             # tokens untouched
        assert steps_warm < steps_cold                  # prefill skipped
        assert eng.last_stats["prefix_hits"] == 1
        # prompt of 9: two full pages, minus the always-re-fed last token
        assert eng.last_stats["prefix_hit_tokens"] == 8
        assert eng.pool.invariant_errors() == []

    def test_concurrent_shared_prefix_isolated(self):
        # two slots decoding from one frozen prefix page must not cross-talk
        cfg = _cfg("yi-9b")
        params = _params(cfg)
        base = _requests(cfg, [(8, 5, 1)])[0]
        r1 = Request(prompt=list(base.prompt) + [11], max_new_tokens=5,
                     eos_id=1)
        r2 = Request(prompt=list(base.prompt) + [17], max_new_tokens=5,
                     eos_id=1)
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                          paged=True, page_size=4)
        eng.generate([base])                            # register the prefix
        shared = eng.generate([r1, r2])
        solo = [ServeEngine(params, cfg, batch_slots=1,
                            max_len=32).generate([r])[0] for r in (r1, r2)]
        assert shared == solo

    def test_prefix_auto_disabled_for_unpageable_state(self):
        # SWA's rolling window is not addressable by absolute position
        cfg = _cfg("mixtral-8x7b")
        eng = ServeEngine(_params(cfg), cfg, batch_slots=1, max_len=32,
                          paged=True, page_size=4)
        assert eng.prefix_cache is False
        cfg2 = _cfg("yi-9b")
        eng2 = ServeEngine(_params(cfg2), cfg2, batch_slots=1, max_len=32,
                           paged=True, page_size=4)
        assert eng2.prefix_cache is True

    def test_supports_prefix_reuse_predicate(self):
        assert KV.supports_prefix_reuse(_cfg("yi-9b"))
        assert not KV.supports_prefix_reuse(_cfg("mixtral-8x7b"))
        assert not KV.supports_prefix_reuse(_cfg("falcon-mamba-7b"))


class TestCacheMemory:
    def test_int8_pages_at_least_3x_below_dense_f32(self):
        cfg = _cfg("yi-9b")
        params = _params(cfg)
        reqs = _requests(cfg, [(5, 3, 1)])
        dense = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                            cache_dtype=jnp.float32)
        dense.generate(reqs)
        paged = ServeEngine(params, cfg, batch_slots=3, max_len=64,
                            paged=True, page_size=4, cache_fmt="int8")
        paged.generate(reqs)
        d = dense.last_stats["cache_bytes_per_slot"]
        p = paged.last_stats["cache_bytes_per_slot"]
        assert d >= 3 * p, f"dense {d} B/slot < 3x paged-int8 {p} B/slot"

    def test_pool_stats_reported(self):
        cfg = _cfg("yi-9b")
        eng = ServeEngine(_params(cfg), cfg, batch_slots=2, max_len=32,
                          paged=True, page_size=4)
        eng.generate(_requests(cfg, [(5, 3, 1)]))
        pool = eng.last_stats["pool"]
        assert pool["pages"] == eng.paged.pool_pages
        assert 0.0 <= pool["occupancy"] <= 1.0
        assert pool["free_pages"] + sum(
            1 for r in eng.pool.refcount if r > 0) == pool["pages"]

    def test_summarize_pytree_accounts_everything(self):
        tree = {"a": jnp.zeros((4, 8), jnp.int8),
                "b": {"c": jnp.zeros((2,), jnp.float32)}}
        s = KV.summarize_pytree(tree)
        assert s["total_bytes"] == 32 + 8 == KV.pytree_bytes(tree)
        assert len(s["leaves"]) == 2
