"""Generation service: paged KV cache, continuous-batching engine,
SLO admission, and the serving fault drills (engine level; the HTTP/
master-proxy drills live in test_serving_service.py)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.common import faults
from determined_tpu.models import gpt as gpt_mod
from determined_tpu.serving import (
    GenerationEngine,
    PagePool,
    PoolExhausted,
    PromptTooLong,
    ServingConfig,
    Shed,
)


def tiny_model():
    """fp32 tiny config: greedy decode must tie-break identically across
    the cached and full-context paths."""
    cfg = gpt_mod.GPTConfig(
        vocab_size=256, n_layers=2, n_heads=4, d_model=64, d_ff=256,
        seq_len=128, remat=False, dtype=jnp.float32,
    )
    model = gpt_mod.GPT(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def make_engine(**overrides) -> GenerationEngine:
    model, params = tiny_model()
    kw = dict(
        page_size=16, num_pages=33, max_pages_per_request=4,
        max_batch_size=4, max_new_tokens=32, prefill_rows=2,
        prefill_seq=32, max_queue_depth=8, default_deadline_s=300.0,
    )
    kw.update(overrides)
    return GenerationEngine(model, params, ServingConfig(**kw))


def assert_greedy(model, params, prompt, generated):
    """The engine's tokens are exactly greedy decoding iff, on ONE
    full-context forward over prompt+generated, every position from the
    last prompt token on argmax-predicts the next emitted token (causal
    masking makes this equivalent to step-by-step greedy, without
    recompiling apply at every grown length)."""
    assert generated, "nothing generated"
    seq = list(prompt) + list(generated)
    logits = model.apply(params, jnp.asarray(np.array([seq], np.int32)))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert int(jnp.argmax(logits[0, i])) == seq[i + 1], (
            f"divergence at position {i}"
        )


class TestServingConfig:
    def test_defaults_valid(self):
        ServingConfig.from_dict({})

    def test_unknown_key_named(self):
        with pytest.raises(ValueError, match="unknown key 'page_sizes'"):
            ServingConfig.from_dict({"page_sizes": 64})

    def test_geometry_checks(self):
        with pytest.raises(ValueError, match="allocatable pool"):
            ServingConfig.from_dict(
                {"num_pages": 4, "max_pages_per_request": 8}
            )
        with pytest.raises(ValueError, match="must be an int >= 1"):
            ServingConfig.from_dict({"page_size": 0})

    def test_expconf_routes_serving_errors(self):
        from determined_tpu.master import expconf

        errs = expconf.validate({
            "entrypoint": "x", "serving": {"page_size": -1, "bogus": 1},
        })
        assert any("serving.page_size" in e for e in errs)
        assert any("bogus" in e for e in errs)
        assert not expconf.validate({
            "entrypoint": "x", "serving": {"page_size": 64},
        })


class TestDecodeKernelConfig:
    def test_decode_kernel_values_validated(self):
        with pytest.raises(ValueError, match="decode_kernel 'fast'"):
            ServingConfig.from_dict({"decode_kernel": "fast"})
        for v in ("auto", "paged", "gather"):
            ServingConfig.from_dict({"decode_kernel": v})

    def test_paged_demands_lane_aligned_page_size(self):
        """The geometry error is named at CONFIG time — not a Mosaic
        shape crash in the middle of a decode iteration."""
        with pytest.raises(ValueError, match="lane granule"):
            ServingConfig.from_dict(
                {"decode_kernel": "paged", "page_size": 96}
            )
        # lane-aligned paged, and misaligned gather/auto, are all fine
        ServingConfig.from_dict({"decode_kernel": "paged", "page_size": 256})
        ServingConfig.from_dict({"decode_kernel": "gather", "page_size": 96})
        ServingConfig.from_dict({"page_size": 96})

    def test_undersized_pool_warns(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING, "determined_tpu.serving"):
            ServingConfig.from_dict(
                {"num_pages": 17, "max_pages_per_request": 4,
                 "max_batch_size": 8}
            )
        assert any(
            "cannot admit a full batch" in r.message for r in caplog.records
        ), caplog.records
        caplog.clear()
        with caplog.at_level(logging.WARNING, "determined_tpu.serving"):
            ServingConfig.from_dict(
                {"num_pages": 33, "max_pages_per_request": 4,
                 "max_batch_size": 8}
            )
        assert not any(
            "cannot admit a full batch" in r.message for r in caplog.records
        )

    def test_expconf_routes_decode_kernel(self):
        from determined_tpu.master import expconf

        errs = expconf.validate({
            "entrypoint": "x",
            "serving": {"decode_kernel": "paged", "page_size": 96},
        })
        assert any("lane granule" in e for e in errs)


class TestPagedDecodePath:
    """Engine-level paged-vs-gather parity: the paged kernel is forced
    on CPU via DTPU_PAGED_ATTN=1 (Pallas interpret mode) so tier-1
    exercises the exact decode path TPU replicas run by default."""

    def _drive(self, eng, scenario):
        """One late-join/early-free churn scenario; returns each
        request's full token list."""
        reqs = []
        long_req = eng.submit([1, 2, 3, 4], max_new_tokens=24)
        stream = long_req.stream(timeout=180)
        kind, _ = next(stream)              # long req is mid-flight
        assert kind == "token"
        # late joiners change the batch composition (and the page
        # table) while the long request keeps decoding
        short = eng.submit([9, 8], max_new_tokens=3)
        tiny = eng.submit([42], max_new_tokens=2)
        assert short.result(timeout=180)["reason"] == "length"
        assert tiny.result(timeout=180)["reason"] == "length"
        # a follow-up admission reuses the freed (now shuffled) pages
        late = eng.submit([7, 7, 2], max_new_tokens=4)
        assert late.result(timeout=180)["reason"] == "length"
        for kind, payload in stream:
            pass
        assert long_req.finish_reason == "length"
        assert eng.pool.pages_in_use == 0
        return {
            "long": list(long_req.tokens), "short": list(short.tokens),
            "tiny": list(tiny.tokens), "late": list(late.tokens),
        }

    def test_paged_matches_gather_through_churn(self, monkeypatch):
        """The tentpole acceptance at engine level: identical greedy
        token streams from both kernels across the SAME late-join/
        early-free page-table churn, and greedy parity with the
        full-context forward."""
        monkeypatch.setenv("DTPU_PAGED_ATTN", "1")
        eng_paged = make_engine()
        assert eng_paged.stats()["decode_kernel"] == "paged"
        assert eng_paged.stats()["decode_backend"] == "interpret"
        eng_paged.start()
        try:
            paged = self._drive(eng_paged, "churn")
            model, params = eng_paged.model, eng_paged.params
        finally:
            eng_paged.stop()
        monkeypatch.setenv("DTPU_PAGED_ATTN", "0")
        eng_gather = make_engine()
        assert eng_gather.stats()["decode_kernel"] == "gather"
        eng_gather.start()
        try:
            gather = self._drive(eng_gather, "churn")
        finally:
            eng_gather.stop()
        assert paged == gather
        assert_greedy(model, params, [1, 2, 3, 4], paged["long"])
        assert_greedy(model, params, [7, 7, 2], paged["late"])

    def test_kill_switch_restores_gather(self, monkeypatch):
        """DTPU_PAGED_ATTN=0 beats even an explicit decode_kernel:
        paged — the PR-6 behavior is one env var away."""
        monkeypatch.setenv("DTPU_PAGED_ATTN", "0")
        eng = make_engine(decode_kernel="paged", page_size=128,
                          num_pages=9, max_pages_per_request=1,
                          prefill_seq=32)
        assert eng.stats()["decode_kernel"] == "gather"
        assert eng.stats()["decode_backend"] == "reference"

    def test_cpu_auto_selects_gather(self, monkeypatch):
        """Off-TPU, both `auto` and an explicit `paged` config resolve
        to the gather fallback (the paged kernel only engages where the
        Pallas path compiles, or under the explicit interpret force).
        Hermetic against an ambient DTPU_PAGED_ATTN (the env override
        beats `auto` by design — e.g. a tier-1 run forcing the paged
        interpret path suite-wide)."""
        monkeypatch.delenv("DTPU_PAGED_ATTN", raising=False)
        for kw in ({}, {"decode_kernel": "paged", "page_size": 128,
                        "num_pages": 9, "max_pages_per_request": 1,
                        "prefill_seq": 32}):
            eng = make_engine(**kw)
            assert eng.stats()["decode_kernel"] == "gather"

    def test_auto_on_misaligned_pool_degrades_to_gather(self, monkeypatch):
        """`auto` on TPU with a page_size that passes validation but
        misses the lane granule must degrade to the gather path with a
        warning — never crash-loop the replica at its first decode
        iteration (the compiled paged kernel would refuse the shape)."""
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        eng = make_engine(page_size=24, num_pages=9,
                          max_pages_per_request=2, prefill_seq=32)
        assert eng.stats()["decode_kernel"] == "gather"

    def test_paged_metrics_emitted(self, monkeypatch):
        """The new observability series move under the paged path:
        pages-read counts live pages only, and the decode-iteration
        histogram files under the active kernel label."""
        from determined_tpu.common.metrics import REGISTRY
        from determined_tpu.serving.engine import KV_PAGES_READ

        monkeypatch.setenv("DTPU_PAGED_ATTN", "1")
        eng = make_engine()
        pages_before = KV_PAGES_READ.value
        hist = REGISTRY.get("dtpu_serving_decode_iteration_seconds")
        count_before = hist.labels("paged")._count
        eng.start()
        try:
            out = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6).result(
                timeout=180
            )
            assert out["reason"] == "length"
        finally:
            eng.stop()
        # 5 decode iterations (first token comes from prefill), one
        # slot, ≤ 1 live page each: 1 page per iteration
        assert KV_PAGES_READ.value >= pages_before + 5
        assert hist.labels("paged")._count >= count_before + 5


class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(9)  # 8 allocatable
        a = pool.alloc(3)
        b = pool.alloc(5)
        assert len(set(a) | set(b)) == 8
        assert 0 not in a + b  # scratch page never handed out
        assert pool.pages_in_use == 8
        with pytest.raises(PoolExhausted):
            pool.alloc(1)
        pool.free(a)
        assert pool.free_pages == 3
        assert pool.alloc(2)

    def test_all_or_nothing(self):
        pool = PagePool(5)
        pool.alloc(2)
        with pytest.raises(PoolExhausted):
            pool.alloc(3)  # only 2 left
        assert pool.free_pages == 2  # nothing partially taken

    def test_double_free_rejected(self):
        pool = PagePool(5)
        pages = pool.alloc(2)
        pool.free(pages)
        with pytest.raises(ValueError, match="double free"):
            pool.free(pages)

    def test_pages_for(self):
        pool = PagePool(5)
        assert pool.pages_for(1, 16) == 1
        assert pool.pages_for(16, 16) == 1
        assert pool.pages_for(17, 16) == 2


class TestEngineGeneration:
    def test_greedy_matches_full_context(self):
        eng = make_engine()
        eng.start()
        try:
            prompt = [5, 9, 3, 14, 7]
            req = eng.submit(prompt, max_new_tokens=8)
            out = req.result(timeout=180)
            assert out["reason"] == "length"
            assert len(out["tokens"]) == 8
            assert_greedy(eng.model, eng.params, prompt, out["tokens"])
            assert eng.pool.pages_in_use == 0  # everything returned
        finally:
            eng.stop()

    def test_packed_prefill_isolation(self):
        """Two prompts admitted into ONE packed prefill batch (they share
        a pack row via segment ids) must each generate exactly what they
        would alone."""
        eng = make_engine()
        eng.start()
        try:
            p1, p2 = [11, 3, 7], [42, 9]
            r1 = eng.submit(p1, max_new_tokens=4)
            r2 = eng.submit(p2, max_new_tokens=4)
            o1, o2 = r1.result(timeout=180), r2.result(timeout=180)
            assert_greedy(eng.model, eng.params, p1, o1["tokens"])
            assert_greedy(eng.model, eng.params, p2, o2["tokens"])
        finally:
            eng.stop()

    def test_late_join_and_early_free(self):
        """The continuous-batching drill at engine level: a late request
        joins a NON-EMPTY batch (no drain) and completes first; its pages
        return to the pool while the long request keeps decoding."""
        from determined_tpu.serving.engine import BATCH_JOINS

        eng = make_engine()
        eng.start()
        try:
            joins_before = BATCH_JOINS.value
            long_req = eng.submit([1, 2, 3, 4], max_new_tokens=30)
            stream = long_req.stream(timeout=180)
            kind, _ = next(stream)          # long req is mid-flight
            assert kind == "token"
            short_req = eng.submit([9, 8], max_new_tokens=2)
            out = short_req.result(timeout=180)
            assert out["reason"] == "length" and len(out["tokens"]) == 2
            # the short request left the batch and freed its pages while
            # the long one is still streaming
            assert BATCH_JOINS.value >= joins_before + 1
            long_done = None
            saw_more_tokens = 0
            for kind, payload in stream:
                if kind == "token":
                    saw_more_tokens += 1
                elif kind == "done":
                    long_done = payload
            assert saw_more_tokens > 0, "long request died with the short one"
            assert long_done is not None and long_done["reason"] == "length"
            assert eng.pool.pages_in_use == 0
            # greedy parity survives batchmates coming and going
            assert_greedy(eng.model, eng.params, [1, 2, 3, 4], long_req.tokens)
        finally:
            eng.stop()

    def test_context_cap_enforced_and_fillable(self):
        eng = make_engine(max_pages_per_request=2)  # 32-token context
        eng.start()
        try:
            # one past the replica context is a client error up front...
            with pytest.raises(PromptTooLong):
                eng.submit([1] * 8, max_new_tokens=25)
            # ...and a request that exactly fills its pages completes
            req = eng.submit([1] * 8, max_new_tokens=24)
            out = req.result(timeout=180)
            assert out["reason"] == "length"
            assert len(out["tokens"]) == 24
        finally:
            eng.stop()


class TestAdmission:
    def test_prompt_too_long_is_client_error(self):
        eng = make_engine()  # prefill_seq=32, context 64
        with pytest.raises(PromptTooLong):
            eng.submit(list(range(40)))         # > prefill_seq
        with pytest.raises(PromptTooLong):
            eng.submit([])
        # page-table cap: 3 pages × 16 = 48-token context
        eng = make_engine(max_pages_per_request=3)
        with pytest.raises(PromptTooLong):
            eng.submit([1] * 30, max_new_tokens=30)  # 60 > 48

    def test_default_token_budget_clamps_to_context(self):
        """The config-default max_new_tokens is a cap, not a promise: a
        request that names NO budget gets the default clamped to the
        remaining context (the documented defaults must serve out of the
        box), while an explicit over-budget ask stays a 400-class error."""
        eng = make_engine(max_new_tokens=100)   # context = 4 pages × 16 = 64
        req = eng.submit([1] * 10)              # engine not started: queued
        assert req.max_new_tokens == 64 - 10
        with pytest.raises(PromptTooLong):
            eng.submit([1] * 10, max_new_tokens=100)

    def test_queue_full_sheds_with_retry_after(self):
        eng = make_engine(max_queue_depth=2)    # engine NOT started
        eng.submit([1], max_new_tokens=1)
        eng.submit([2], max_new_tokens=1)
        with pytest.raises(Shed) as e:
            eng.submit([3], max_new_tokens=1)
        assert e.value.retry_after > 0
        assert "queue full" in str(e.value)

    def test_expired_deadline_sheds(self):
        eng = make_engine()
        with pytest.raises(Shed, match="deadline"):
            eng.submit([1, 2], deadline_s=-1.0)

    def test_deadline_cuts_off_mid_generation(self):
        eng = make_engine()
        # Every decode iteration is held 0.2 s: the 29 that 30 tokens
        # need take 5.8 s at the least, on any machine, so a deadline of
        # 2 s falls mid-generation by construction (an idle engine
        # admits in milliseconds, and a deadline that a slow compile
        # overruns cuts off at the first decode iteration).
        plan = faults.FaultPlan(
            {"serving.decode": faults.FaultSpec(latency_s=0.2)})
        eng.start()
        try:
            with faults.plan_active(plan):
                req = eng.submit(
                    [1, 2, 3], max_new_tokens=30, deadline_s=2.0)
                out = req.result(timeout=180)
            assert out["reason"] == "deadline"
            assert 0 < len(out["tokens"]) < 30
            assert eng.pool.pages_in_use == 0
        finally:
            eng.stop()


class TestServingFaultDrills:
    def test_admission_fault_sheds_deterministically(self):
        from determined_tpu.serving.engine import SHED

        eng = make_engine()
        before = SHED.labels("fault").value
        plan = faults.FaultPlan({"serving.admission": faults.FaultSpec(failures=1)})
        with faults.plan_active(plan):
            with pytest.raises(Shed, match="injected"):
                eng.submit([1, 2], max_new_tokens=1)
            req = eng.submit([1, 2], max_new_tokens=1)  # heals after 1
        assert req is not None
        assert SHED.labels("fault").value == before + 1

    def test_decode_fault_fails_streams_and_frees_pages(self):
        from determined_tpu.serving.engine import DECODE_FAILURES

        eng = make_engine()
        before = DECODE_FAILURES.value
        plan = faults.FaultPlan({"serving.decode": faults.FaultSpec(failures=1)})
        eng.start()
        try:
            with faults.plan_active(plan):
                req = eng.submit([4, 5, 6], max_new_tokens=10)
                events = list(req.stream(timeout=180))
            # prefill streamed the first token, then the injected decode
            # failure ended the stream with an SSE-able error event
            kinds = [k for k, _ in events]
            assert kinds[0] == "token"
            assert kinds[-1] == "error"
            assert "decode step failed" in events[-1][1]
            assert DECODE_FAILURES.value == before + 1
            assert eng.pool.pages_in_use == 0  # pages freed on failure
            # the engine survives: a fresh request completes normally
            out = eng.submit([4, 5, 6], max_new_tokens=2).result(timeout=180)
            assert out["reason"] == "length"
        finally:
            eng.stop()

    def test_page_alloc_fault_is_pool_exhaustion(self):
        from determined_tpu.serving.engine import SHED

        eng = make_engine()
        before = SHED.labels("pages").value
        plan = faults.FaultPlan(
            {"serving.page_alloc": faults.FaultSpec(failures=1)}
        )
        eng.start()
        try:
            with faults.plan_active(plan):
                req = eng.submit([7, 8], max_new_tokens=2)
                events = list(req.stream(timeout=180))
            assert events[-1][0] == "error"
            assert "page pool exhausted" in events[-1][1]
            assert SHED.labels("pages").value == before + 1
            # pool untouched (all-or-nothing), next request is fine
            assert eng.pool.pages_in_use == 0
            out = eng.submit([7, 8], max_new_tokens=2).result(timeout=180)
            assert out["reason"] == "length"
        finally:
            eng.stop()

    def test_real_crash_recovers_slots_pages_and_streams(self):
        """A REAL (non-injected) exception in the engine loop must not
        leak the in-flight requests' slots/pages or leave their clients
        hanging: the loop-level recovery evicts them like the injected
        serving.decode drill does, and the engine keeps serving."""
        from determined_tpu.serving.engine import DECODE_FAILURES

        eng = make_engine()
        before = DECODE_FAILURES.value
        real_decode = eng._decode_fn
        calls = {"n": 0}

        def flaky_decode(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("synthetic device failure")
            return real_decode(*args, **kwargs)

        # After start(): its warm-up compiles by calling the real step,
        # and a failure THERE is fatal by design — this drill is about a
        # fault at run time.
        eng.start()
        eng._decode_fn = flaky_decode
        try:
            req = eng.submit([4, 5, 6], max_new_tokens=10)
            events = list(req.stream(timeout=180))
            kinds = [k for k, _ in events]
            assert kinds[0] == "token"         # prefill's first token
            assert kinds[-1] == "error"        # crash closed the stream
            assert "engine iteration failed" in events[-1][1]
            assert DECODE_FAILURES.value == before + 1
            assert eng.pool.pages_in_use == 0  # no page leak
            assert all(r is None for r in eng._slots)  # no slot leak
            # the engine survives: a fresh request completes normally
            out = eng.submit([4, 5, 6], max_new_tokens=2).result(timeout=180)
            assert out["reason"] == "length"
        finally:
            eng.stop()
