"""Oracle-driven serve suite: every serving component pinned token-exact.

Layered oracles, cheapest substrate proving each layer:

1. ``DecodeEngine`` greedy decode == per-position argmax of the full
   ``forward`` on the same tokens (kv-cache-vs-recompute), for every decoder
   family including the sliding-window ring cache;
2. ``ContinuousEngine`` == a per-request sequential ``DecodeEngine`` run —
   token-exact per request across a seeded admit/evict schedule, so the
   paged cache, the chunked-prefill mix and the scheduler cannot corrupt
   anything the simple engine would not;
3. property tests (hypothesis, via the ``_hyp`` shim) for the page
   allocator and the scheduler's page-table invariants, plus a bit-identity
   pin that evict-then-admit page reuse cannot perturb OTHER slots;
4. a subprocess TP test: the ``--tp 2`` engine on the 8-device CPU mesh is
   token-identical to the TP-free one (greedy AND temperature sampling),
   and the lowered step's HLO census passes
   ``analysis.contract.serve_step_contract`` — every collective reduces
   over the model axes only.

Also pinned: the linear-cache overflow guard (the silent clamp-overwrite
this suite regression-demonstrates) and the engine's timing stats keys.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models import dense
from repro.serve import (
    NULL_PAGE,
    ContinuousConfig,
    ContinuousEngine,
    DecodeEngine,
    PageAllocator,
    Request,
    Scheduler,
    ServeConfig,
    pages_needed,
)
from repro.serve import cache as cache_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ModelConfig(
    name="tiny-swiglu", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=64, tie_embeddings=True, act="swiglu",
)


def _build(cfg):
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _logits(model, params, tokens):
    out = model.forward(params, {"tokens": tokens})
    return out[0] if isinstance(out, tuple) else out


def _assert_greedy_trajectory(model, params, prompt, gen):
    """Cache-free oracle: ONE teacher-forced forward over prompt + gen must
    reproduce every generated token as the argmax at its source position
    (causality makes this equivalent to re-running the forward per token,
    at 1/max_new the trace count)."""
    toks = [int(t) for t in prompt] + [int(t) for t in gen]
    logits = np.asarray(
        _logits(model, params, jnp.asarray([toks], jnp.int32))[0], np.float32
    )
    P = len(prompt)
    for i, tok in enumerate(gen):
        assert int(np.argmax(logits[P - 1 + i])) == int(tok), (i, tok)


def _make_requests(rng, n, vocab, p_lo=3, p_hi=11, g_lo=2, g_hi=7):
    reqs = []
    for rid in range(n):
        P = int(rng.integers(p_lo, p_hi))
        prompt = rng.integers(0, vocab, size=P).astype(np.int32)
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new=int(rng.integers(g_lo, g_hi))))
    return reqs


# ---------------------------------------------------------------------------
# 1. DecodeEngine vs recompute oracle (every decoder family)
# ---------------------------------------------------------------------------


class TestDecodeEngineOracle:
    @pytest.mark.parametrize(
        "arch",
        [
            "olmo-1b",            # dense MHA
            "qwen3-4b",           # dense GQA + qk-norm + tied embeddings
            "recurrentgemma-2b",  # RG-LRU recurrence + local attention
            "xlstm-1.3b",         # mLSTM recurrent decode
            "deepseek-moe-16b",   # MoE dispatch
            "deepseek-v2-lite",   # latent attention + dropless MoE
        ],
    )
    def test_greedy_equals_forward_argmax(self, arch):
        cfg = get_config(arch, reduced=True)
        model, params = _build(cfg)
        eng = DecodeEngine(model, params, ServeConfig(max_len=32))
        prompts = np.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 5)),
            np.int32,
        )
        gen, _ = eng.generate(jnp.asarray(prompts), 6)
        gen = np.asarray(gen)
        for b in range(2):
            _assert_greedy_trajectory(model, params, prompts[b], gen[b])

    def test_sliding_window_ring_cache(self):
        """Generate PAST the window so the ring cache wraps: tokens must
        still match the forward oracle (same window mask, full recompute)."""
        cfg = get_config("qwen3-4b", reduced=True).replace(window=8)
        model, params = _build(cfg)
        eng = DecodeEngine(model, params, ServeConfig(max_len=64))
        prompts = np.asarray(
            np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 5)),
            np.int32,
        )
        gen, _ = eng.generate(jnp.asarray(prompts), 8)  # 5 + 8 > window
        gen = np.asarray(gen)
        for b in range(2):
            _assert_greedy_trajectory(model, params, prompts[b], gen[b])

    def test_linear_cache_overflow_raises(self):
        """Non-window models must refuse to generate past max_len."""
        model, params = _build(TINY)
        eng = DecodeEngine(model, params, ServeConfig(max_len=8))
        prompts = jnp.zeros((1, 5), jnp.int32)
        with pytest.raises(ValueError, match="max_len"):
            eng.generate(prompts, 6)
        # exactly at capacity is fine
        eng.generate(prompts, 3)

    def test_overflow_clamp_corrupts_logits(self):
        """Regression for the guard above: the raw decode path CLAMPS its
        write slot at the last cache row (OOB protection), so stepping past
        max_len silently overwrites that row's KV — the resulting logits
        diverge from the recompute oracle.  This is the failure mode the
        engine's eager validation exists to keep unreachable."""
        model, params = _build(TINY)
        S, max_len = 10, 6
        tokens = np.random.default_rng(5).integers(0, TINY.vocab_size, (1, S))
        tokens = jnp.asarray(tokens, jnp.int32)
        cache = model.init_cache(1, max_len)
        step = jax.jit(model.decode_step)
        for t in range(S):
            logits, cache = step(params, cache, tokens[:, t : t + 1])
        ref = _logits(model, params, tokens)[:, -1]
        assert not np.allclose(
            np.asarray(logits[:, -1], np.float32),
            np.asarray(ref, np.float32),
            rtol=1e-3, atol=1e-3,
        )

    def test_timing_stats_keys(self):
        model, params = _build(TINY)
        eng = DecodeEngine(model, params, ServeConfig(max_len=32))
        _, stats = eng.generate(jnp.zeros((2, 4), jnp.int32), 5)
        for k in ("prefill_s", "decode_s", "prefill_tps", "decode_tps",
                  "tokens_per_s"):
            assert k in stats, k
            assert np.isfinite(stats[k]) and stats[k] > 0, (k, stats[k])


# ---------------------------------------------------------------------------
# 2. ContinuousEngine vs sequential DecodeEngine oracle
# ---------------------------------------------------------------------------


def _decode_engine_oracle(model, params, reqs, max_len=64):
    eng = DecodeEngine(model, params, ServeConfig(max_len=max_len))
    out = {}
    for r in reqs:
        gen, _ = eng.generate(jnp.asarray(r.prompt)[None, :], r.max_new)
        out[r.rid] = list(np.asarray(gen)[0])
    return out


class TestContinuousEngineOracle:
    @pytest.mark.parametrize("policy", ["continuous", "static"])
    def test_matches_sequential_oracle(self, policy):
        """6 requests through 2 slots (chunk 4, page 4): the schedule admits
        and evicts mid-flight, and every request's tokens equal a solo
        DecodeEngine run of that request."""
        model, params = _build(TINY)
        reqs = _make_requests(np.random.default_rng(0), 6, TINY.vocab_size)
        oracle = _decode_engine_oracle(model, params, reqs)
        eng = ContinuousEngine(
            model, params,
            ContinuousConfig(num_slots=2, chunk=4, page_size=4, num_pages=16,
                             max_len=32, policy=policy),
        )
        results, stats = eng.run(reqs)
        for r in reqs:
            assert list(results[r.rid]) == oracle[r.rid], r.rid
        for k in ("tokens_per_s", "latency_p50", "latency_p99",
                  "ttft_p50", "ttft_p99"):
            assert np.isfinite(stats[k]), (k, stats[k])
        assert stats["generated_tokens"] == sum(r.max_new for r in reqs)

    def test_scarce_pages_stall_admission_not_correctness(self):
        """A pool barely larger than one request's worst case serializes
        admission through the reservation check — tokens still exact."""
        model, params = _build(TINY)
        reqs = _make_requests(np.random.default_rng(1), 4, TINY.vocab_size)
        oracle = _decode_engine_oracle(model, params, reqs)
        worst = max(pages_needed(r.prompt_len + r.max_new - 1, 4) for r in reqs)
        eng = ContinuousEngine(
            model, params,
            ContinuousConfig(num_slots=2, chunk=4, page_size=4,
                             num_pages=worst + 1, max_len=32),
        )
        results, _ = eng.run(reqs)
        for r in reqs:
            assert list(results[r.rid]) == oracle[r.rid], r.rid

    def test_pallas_flash_prefill(self):
        """attention_impl='pallas' routes the pure-prefill step through the
        flash kernel (interpret mode on CPU); tokens stay oracle-exact."""
        cfg = TINY.replace(name="tiny-swiglu-pallas", attention_impl="pallas")
        model, params = _build(cfg)
        # prompts fit one chunk: the first step is pure prefill_self
        reqs = _make_requests(np.random.default_rng(2), 2, cfg.vocab_size,
                              p_lo=3, p_hi=5, g_lo=2, g_hi=4)
        oracle = _decode_engine_oracle(model, params, reqs)
        eng = ContinuousEngine(
            model, params,
            ContinuousConfig(num_slots=2, chunk=4, page_size=4, num_pages=16,
                             max_len=32),
        )
        results, _ = eng.run(reqs)
        for r in reqs:
            assert list(results[r.rid]) == oracle[r.rid], r.rid

    def test_rejects_oversized_request(self):
        model, params = _build(TINY)
        eng = ContinuousEngine(
            model, params,
            ContinuousConfig(num_slots=2, chunk=4, page_size=4, num_pages=16,
                             max_len=16),
        )
        bad = [Request(rid=0, prompt=np.zeros(12, np.int32), max_new=8)]
        with pytest.raises(ValueError, match="max_len"):
            eng.run(bad)

    def test_rejects_non_dense_family(self):
        cfg = get_config("xlstm-1.3b", reduced=True)
        model, params = _build(cfg)
        with pytest.raises(ValueError, match="dense"):
            ContinuousEngine(model, params, ContinuousConfig())


# ---------------------------------------------------------------------------
# 3. paged-cache properties
# ---------------------------------------------------------------------------


class TestPageAllocatorProperties:
    @given(st.integers(0, 10**9))
    @settings(max_examples=30, deadline=None)
    def test_random_alloc_free_invariants(self, seed):
        rng = np.random.default_rng(seed)
        num_pages = int(rng.integers(4, 24))
        alloc = PageAllocator(num_pages)
        held: list[int] = []
        for _ in range(40):
            if held and rng.random() < 0.4:
                k = int(rng.integers(1, len(held) + 1))
                batch = [held.pop(int(rng.integers(len(held)))) for _ in range(k)]
                alloc.free(batch)
            else:
                n = int(rng.integers(1, 4))
                if not alloc.can_reserve(n):
                    continue
                alloc.reserve(n)
                pages = alloc.allocate(n)
                # never the null page, always in range, never double-handed
                assert all(1 <= p <= num_pages for p in pages)
                assert NULL_PAGE not in pages
                assert not set(pages) & set(held)
                held.extend(pages)
            assert len(set(held)) == len(held)
        alloc.free(held)
        # everything returned: the whole pool is allocatable again
        alloc.reserve(num_pages)
        again = alloc.allocate(num_pages)
        assert sorted(again) == list(range(1, num_pages + 1))

    def test_double_free_raises(self):
        alloc = PageAllocator(4)
        alloc.reserve(2)
        pages = alloc.allocate(2)
        alloc.free(pages)
        with pytest.raises(ValueError, match="double free"):
            alloc.free([pages[0]])

    def test_null_page_never_freed_or_allocated(self):
        alloc = PageAllocator(4)
        with pytest.raises(ValueError, match="invalid page"):
            alloc.free([NULL_PAGE])
        alloc.reserve(4)
        assert NULL_PAGE not in alloc.allocate(4)


class TestSchedulerProperties:
    @given(st.integers(0, 10**9))
    @settings(max_examples=20, deadline=None)
    def test_page_table_covers_exactly_pos(self, seed):
        """Drive a full random serve schedule with fake sampled tokens: at
        every step each slot's table maps exactly ``pages_needed(pos)``
        pages after commit, pages are disjoint across slots, and the pool
        drains back to full when the queue empties."""
        rng = np.random.default_rng(seed)
        page_size = int(rng.integers(2, 6))
        num_pages = 8
        max_len = min(16, num_pages * page_size)
        sched = Scheduler(num_slots=3, chunk=4, page_size=page_size,
                          num_pages=num_pages, max_len=max_len)
        reqs = []
        for rid in range(int(rng.integers(1, 7))):
            cap = max_len - 1
            P = int(rng.integers(1, cap))
            reqs.append(Request(
                rid=rid,
                prompt=rng.integers(0, 64, size=P).astype(np.int32),
                max_new=int(rng.integers(1, max_len - P + 1)),
            ))
        sched.submit(reqs)
        for _ in range(500):
            if sched.done():
                break
            sched.admit(0.0)
            plan = sched.plan()
            assert plan is not None
            # planned coverage: table rows hold exactly the pages the new
            # pos will need, disjoint across slots, never the null page
            mapped = []
            for b in range(3):
                row = plan.page_table[b]
                n_mapped = int((row != NULL_PAGE).sum())
                expect = pages_needed(int(plan.pos[b] + plan.num_new[b]),
                                      page_size)
                assert n_mapped == expect, (b, n_mapped, expect)
                mapped.extend(row[row != NULL_PAGE].tolist())
            assert len(set(mapped)) == len(mapped)
            assert all(1 <= p <= num_pages for p in mapped)
            sched.commit(rng.integers(0, 64, size=3).astype(np.int32), 0.0)
        assert sched.done()
        # all pages free, no reservation leaked
        assert sched.allocator.available == num_pages
        for r in reqs:
            assert len(r.generated) == r.max_new


class TestEvictAdmitBitIdentity:
    def test_other_slots_unperturbed(self):
        """Evicting slot 0 and admitting a NEW request into its reused pages
        must leave slot 1's logits bit-identical — the null-page scatter and
        per-slot page disjointness guarantee isolation."""
        model, params = _build(TINY)
        page_size, num_pages, pps = 4, 8, 2
        k0, v0 = cache_lib.init_pools(TINY, num_pages, page_size)
        rng = np.random.default_rng(7)
        prompt_a = jnp.asarray(rng.integers(0, 64, (4,)), jnp.int32)
        prompt_b = jnp.asarray(rng.integers(0, 64, (4,)), jnp.int32)
        prompt_c = jnp.asarray(rng.integers(0, 64, (4,)), jnp.int32)

        step = jax.jit(
            lambda *a, **k: dense.paged_step(TINY, *a, **k),
            static_argnames=("prefill_self",),
        )
        # step 1: prefill slot0 (pages 1,2) and slot1 (pages 3,4)
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        pos = jnp.zeros(2, jnp.int32)
        num_new = jnp.asarray([4, 4], jnp.int32)
        tokens = jnp.stack([prompt_a, prompt_b])
        _, k1, v1 = step(params, k0, v0, table, pos, num_new, tokens,
                         prefill_self=True)

        def decode_slot1(table0_row, num_new0, tokens0, k, v):
            table2 = jnp.asarray([table0_row, [3, 4]], jnp.int32)
            logits, _, _ = step(
                params, k, v, table2,
                jnp.asarray([0, 4], jnp.int32),
                jnp.asarray([num_new0, 1], jnp.int32),
                jnp.stack([tokens0, jnp.asarray([9, 0, 0, 0], jnp.int32)]),
                prefill_self=False,
            )
            return np.asarray(logits[1], np.float32)

        # control: slot0 evicted (row unmapped, nothing admitted)
        control = decode_slot1([NULL_PAGE, NULL_PAGE], 0,
                               jnp.zeros(4, jnp.int32), k1, v1)
        # variant: slot0's freed pages 1,2 reused by a fresh admit
        variant = decode_slot1([1, 2], 4, prompt_c, k1, v1)
        assert np.array_equal(control, variant)


# ---------------------------------------------------------------------------
# 4. tensor-parallel serve (subprocess: 8-device CPU mesh)
# ---------------------------------------------------------------------------


TP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.launch.mesh import make_spmd_layout
from repro.serve import ContinuousConfig, ContinuousEngine, Request
from repro.analysis import contract, hlo, rules
from repro.distributed import spmd
from repro.serve import cache as cache_lib

CFG = ModelConfig(
    name="tiny-swiglu", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=64, tie_embeddings=True, act="swiglu",
)
model = build_model(CFG)
params = model.init(jax.random.PRNGKey(0))
layout = make_spmd_layout(1, 2)

rng = np.random.default_rng(1)
protos = []
for rid in range(4):
    P = int(rng.integers(3, 11))
    protos.append((rid, rng.integers(0, CFG.vocab_size, size=P).astype(np.int32)))

def reqs():
    return [Request(rid=rid, prompt=p, max_new=4) for rid, p in protos]

for temp, marker in ((0.0, "TP-MATCH-GREEDY"), (0.7, "TP-MATCH-SAMPLED")):
    ccfg = ContinuousConfig(num_slots=2, chunk=4, page_size=4, num_pages=16,
                            max_len=32, temperature=temp)
    ref, _ = ContinuousEngine(model, params, ccfg).run(reqs())
    tp, _ = ContinuousEngine(model, params, ccfg, layout=layout).run(reqs())
    assert all(list(tp[r]) == list(ref[r]) for r, _ in protos), (temp, tp, ref)
    print(marker, "OK")

# HLO census of the TP mixed step: model-axis collectives only
pool_shape = cache_lib.pool_shape(CFG, 16, 4)
step = spmd.make_paged_serve_step(CFG, layout, params, pool_shape,
                                  prefill_self=False, temperature=0.0)
z = jnp.zeros(pool_shape, CFG.dtype)
lowered = step.lower(
    params, z, z, jnp.zeros((2, 8), jnp.int32), jnp.zeros(2, jnp.int32),
    jnp.zeros(2, jnp.int32), jnp.zeros((2, 1), jnp.int32),
    jax.random.PRNGKey(0),
)
text = hlo.lowered_hlo_text(lowered)
violations = rules.check_census(contract.serve_step_contract(layout),
                                layout.mesh, text)
assert not violations, violations
assert hlo.collective_ops(text), "TP step lowered no collectives at all?"
print("SERVE-CENSUS OK")
"""


class TestTensorParallelServe:
    def test_tp2_engine_token_identical_and_census(self):
        env = {
            "PYTHONPATH": os.path.join(REPO_ROOT, "src"),
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "JAX_PLATFORMS": "cpu",
            "HOME": os.environ.get("HOME", "/tmp"),
        }
        res = subprocess.run(
            [sys.executable, "-c", TP_SCRIPT],
            env=env, cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=600,
        )
        assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
        for marker in ("TP-MATCH-GREEDY OK", "TP-MATCH-SAMPLED OK",
                       "SERVE-CENSUS OK"):
            assert marker in res.stdout, (marker, res.stdout)
