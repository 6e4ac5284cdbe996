"""Speculative decoding on the paged geometry: the greedy-parity
contract (spec-on token streams bit-identical to spec-off, on BOTH
decode kernels, through prefix-cache hits and late-join/early-free
churn), the rollback-rewind invariant, the `serving.speculation` fault
drill, config validation, and the prompt-lookup proposer units."""
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.common import faults
from determined_tpu.models import gpt as gpt_mod
from determined_tpu.serving import GenerationEngine, ServingConfig
from determined_tpu.serving.speculation import propose_ngram_draft


def tiny_model():
    """fp32 tiny config: greedy argmax must tie-break identically across
    the speculative and plain decode paths."""
    cfg = gpt_mod.GPTConfig(
        vocab_size=256, n_layers=2, n_heads=4, d_model=64, d_ff=256,
        seq_len=128, remat=False, dtype=jnp.float32,
    )
    model = gpt_mod.GPT(cfg)
    return model, model.init(jax.random.PRNGKey(0))


_MODEL, _PARAMS = None, None


def shared_model():
    global _MODEL, _PARAMS
    if _MODEL is None:
        _MODEL, _PARAMS = tiny_model()
    return _MODEL, _PARAMS


def make_engine(**overrides) -> GenerationEngine:
    model, params = shared_model()
    kw = dict(
        page_size=16, num_pages=33, max_pages_per_request=4,
        max_batch_size=4, max_new_tokens=32, prefill_rows=2,
        prefill_seq=32, max_queue_depth=8, default_deadline_s=300.0,
    )
    kw.update(overrides)
    return GenerationEngine(model, params, ServingConfig(**kw))


def assert_greedy(model, params, prompt, generated):
    """One full-context forward argmax-predicts every emitted token."""
    assert generated, "nothing generated"
    seq = list(prompt) + list(generated)
    logits = model.apply(params, jnp.asarray(np.array([seq], np.int32)))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert int(jnp.argmax(logits[0, i])) == seq[i + 1], (
            f"divergence at position {i}"
        )


@contextlib.contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


#: n-gram-rich prompts: trailing grams recur inside each prompt, so the
#: prompt-lookup proposer fires from the very first decode iteration.
LONG_PROMPT = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
SHORT_PROMPT = [9, 8, 9, 8, 9]
LATE_PROMPT = [7, 7, 2, 7, 7]


def _churn_streams(eng):
    """The late-join/early-free churn scenario; returns every request's
    full token list (plus a prefix-cache-hit request when cache is on).
    Page tables shuffle mid-flight: the long request keeps decoding
    while batch-mates join, finish, free, and their pages get reused."""
    long_req = eng.submit(LONG_PROMPT, max_new_tokens=24)
    stream = long_req.stream(timeout=180)
    kind, _ = next(stream)                 # long req is mid-flight
    assert kind == "token"
    short = eng.submit(SHORT_PROMPT, max_new_tokens=3)
    tiny = eng.submit([42], max_new_tokens=2)
    assert short.result(timeout=180)["reason"] == "length"
    assert tiny.result(timeout=180)["reason"] == "length"
    late = eng.submit(LATE_PROMPT, max_new_tokens=6)
    assert late.result(timeout=180)["reason"] == "length"
    for _kind, _payload in stream:
        pass
    assert long_req.finish_reason == "length"
    out = {
        "long": list(long_req.tokens), "short": list(short.tokens),
        "tiny": list(tiny.tokens), "late": list(late.tokens),
    }
    if eng.prefix_cache is not None:
        # A request re-walking the long request's written history MUST
        # hit the radix cache — speculation's length bookkeeping (only
        # ACCEPTED positions count) keeps adopted pages garbage-free.
        hit_prompt = (LONG_PROMPT + out["long"])[:18]
        hit = eng.submit(hit_prompt, max_new_tokens=4)
        assert hit.result(timeout=180)["reason"] == "length"
        assert eng.prefix_cache.hits > 0, "prefix cache never hit"
        out["hit"] = list(hit.tokens)
    # all pages either back on the free list or adopted by the radix
    # tree — speculation must not leak a single page through churn
    held = len(eng.prefix_cache) if eng.prefix_cache is not None else 0
    assert eng.pool.pages_in_use == held
    return out


def _run(kernel: str, cache: str, speculation):
    with _env(DTPU_PAGED_ATTN="1" if kernel == "paged" else "0"):
        eng = make_engine(prefix_cache=cache, speculation=speculation)
        eng.start()
        try:
            streams = _churn_streams(eng)
            stats = eng.stats()["speculation"]
        finally:
            eng.stop()
    return streams, stats


_BASELINES = {}


def _baseline(kernel: str, cache: str):
    key = (kernel, cache)
    if key not in _BASELINES:
        _BASELINES[key] = _run(kernel, cache, {"mode": "off"})[0]
    return _BASELINES[key]


class TestGreedyParity:
    @pytest.mark.parametrize("draft_len", [1, 4, 8])
    @pytest.mark.parametrize("cache", ["off", "on"])
    @pytest.mark.parametrize("kernel", ["gather", "paged"])
    def test_spec_streams_bit_identical(self, kernel, cache, draft_len):
        """The tentpole contract: spec-on greedy token streams are
        bit-identical to spec-off on both decode kernels, across
        prefix-cache on/off, late-join/early-free churn, and every
        supported draft length — AND speculation really fired (a parity
        proof over zero proposals would be vacuous)."""
        base = _baseline(kernel, cache)
        streams, stats = _run(kernel, cache, {
            "mode": "ngram", "draft_len": draft_len, "min_match": 2,
        })
        assert streams == base
        assert stats["proposed_tokens"] > 0, "speculation never proposed"
        assert stats["accepted_tokens"] > 0, "speculation never accepted"
        model, params = shared_model()
        assert_greedy(model, params, LONG_PROMPT, streams["long"])

    def test_mixed_batch_sampled_and_greedy_slots(self):
        """Sampled slots never speculate but share the ONE compiled spec
        step (q_lens=1); their streams match the spec-off engine's
        sampled streams seeded identically, and greedy batch-mates keep
        their parity."""
        outs = {}
        for spec in ({"mode": "off"},
                     {"mode": "ngram", "draft_len": 4, "min_match": 2}):
            eng = make_engine(speculation=spec)
            eng.start()
            try:
                greedy_req = eng.submit(LONG_PROMPT, max_new_tokens=10)
                hot = eng.submit([6, 6, 6], max_new_tokens=8,
                                 temperature=0.7)
                assert greedy_req.result(timeout=180)["reason"] == "length"
                assert hot.result(timeout=180)["reason"] == "length"
                outs[spec["mode"]] = (
                    list(greedy_req.tokens), list(hot.tokens),
                )
                if spec["mode"] == "ngram":
                    assert eng.stats()["speculation"]["proposed_tokens"] > 0
            finally:
                eng.stop()
        # Greedy stream: bit-identical. The sampled stream is NOT part
        # of the parity contract (verify reshapes the sampling step's
        # flash geometry), but it must exist and be in-vocab.
        assert outs["off"][0] == outs["ngram"][0]
        assert len(outs["ngram"][1]) == 8


class TestRollback:
    def test_rejected_tail_rewind_equals_never_speculated(self, monkeypatch):
        """Force EVERY draft wrong (the proposer is monkeypatched to
        propose exactly not-the-next-token): every iteration writes a
        draft K/V tail, rejects it, and rewinds. The stream must still
        be bit-identical to the never-speculated baseline — the
        rejected tail is invisible — and the counters must show pure
        rollback. Pages never leak: rollback is lengths bookkeeping
        only, the free list is untouched."""
        from determined_tpu.serving import engine as engine_mod

        prompt = [3, 1, 4, 1, 5]
        base, _ = _run("gather", "off", {"mode": "off"})
        eng = make_engine()  # spec-off reference for THIS prompt
        eng.start()
        try:
            ref = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
        finally:
            eng.stop()
        base_tokens = ref["tokens"]

        def wrong_draft(history, draft_len, min_match):
            k = len(history) - len(prompt)   # tokens emitted so far
            if k >= len(base_tokens):
                return []
            return [(base_tokens[k] + 1) % 256]

        monkeypatch.setattr(engine_mod, "propose_ngram_draft", wrong_draft)
        eng = make_engine(
            speculation={"mode": "ngram", "draft_len": 4, "min_match": 2},
        )
        eng.start()
        try:
            out = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
            stats = eng.stats()["speculation"]
        finally:
            eng.stop()
        assert out["tokens"] == base_tokens
        assert stats["proposed_tokens"] > 0
        assert stats["accepted_tokens"] == 0
        assert stats["rollback_tokens"] == stats["proposed_tokens"]
        assert eng.pool.pages_in_use == 0

    @pytest.mark.parametrize("kernel,interpret", [
        ("gather", False), ("paged", True),
    ])
    def test_rewind_state_model_level(self, kernel, interpret):
        """decode_kv with a corrupted draft: the accepted-prefix
        rows are undisturbed, and continuing PLAIN decode from the
        spec-written cache at the rewound length reproduces the
        never-speculated stream exactly — lengths + page table after a
        rejected tail ARE the never-speculated state."""
        from determined_tpu.batch_inference import pack_sequences

        model, params = shared_model()
        cfg = model.config
        ps, n_pages, per, B = 16, 33, 4, 3
        ck = jnp.zeros(
            (cfg.n_layers, n_pages, ps, cfg.n_heads, cfg.head_dim),
            cfg.dtype,
        )
        cv = jnp.zeros_like(ck)
        pt = np.zeros((B, per), np.int32)
        pt[0] = [1, 2, 3, 4]
        pt[1] = [5, 6, 7, 8]
        batch = list(pack_sequences(
            [[1, 2, 3, 4], [9, 8]], 32, 2, overflow="error",
        ))[0]
        positions = np.zeros_like(batch["tokens"])
        positions[0, :4] = np.arange(4)
        positions[1, :2] = np.arange(2)
        logits, k_l, v_l = model.prefill_kv(
            params, jnp.asarray(batch["tokens"]), jnp.asarray(positions),
            jnp.asarray(batch["segment_ids"]),
        )
        for row, page in ((0, 1), (1, 5)):
            ck = ck.at[:, page].set(k_l[:, row, :16])
            cv = cv.at[:, page].set(v_l[:, row, :16])
        last0 = int(np.argmax(np.asarray(logits)[0, 3]))
        last1 = int(np.argmax(np.asarray(logits)[1, 1]))

        def plain(ckx, cvx, lengths, last, steps):
            active = np.array([1, 1, 0], bool)
            stream = [[], []]
            for _ in range(steps):
                lg, ckx, cvx = model.decode_kv(
                    params, jnp.asarray(last)[:, None],
                    jnp.asarray(lengths), jnp.ones((B,), jnp.int32),
                    jnp.asarray(active), ckx, cvx, jnp.asarray(pt),
                    q_pad=1, kernel=kernel, interpret=interpret,
                )
                nxt = np.argmax(np.asarray(lg)[:, 0], -1)
                stream[0].append(int(nxt[0]))
                stream[1].append(int(nxt[1]))
                last = nxt.astype(np.int32)
                lengths = lengths + 1
            return stream, ckx, cvx

        base, _, _ = plain(
            ck, cv, np.array([4, 2, 0], np.int32),
            np.array([last0, last1, 0], np.int32), 5,
        )
        # Speculate on slot 0 with the TRUE continuation, then corrupt
        # draft position 2 — rows 0..1 must stay valid.
        toks = np.zeros((B, 4), np.int32)
        toks[0, 0] = last0
        toks[0, 1:] = base[0][:3]
        toks[1, 0] = last1
        q_lens = np.array([4, 1, 1], np.int32)
        lg, cks, cvs = model.decode_kv(
            params, jnp.asarray(toks),
            jnp.asarray(np.array([4, 2, 0], np.int32)),
            jnp.asarray(q_lens), jnp.asarray(np.array([1, 1, 0], bool)),
            ck, cv, jnp.asarray(pt), q_pad=1, kernel=kernel,
            interpret=interpret,
        )
        g = np.argmax(np.asarray(lg), -1)
        assert g[0].tolist() == base[0][:4]      # full verify == plain
        assert int(g[1, 0]) == base[1][0]        # plain slot in mix
        toks2 = toks.copy()
        toks2[0, 2] = (toks[0, 2] + 1) % 256
        lg2, cks2, cvs2 = model.decode_kv(
            params, jnp.asarray(toks2),
            jnp.asarray(np.array([4, 2, 0], np.int32)),
            jnp.asarray(q_lens), jnp.asarray(np.array([1, 1, 0], bool)),
            ck, cv, jnp.asarray(pt), q_pad=1, kernel=kernel,
            interpret=interpret,
        )
        g2 = np.argmax(np.asarray(lg2), -1)
        assert g2[0, :2].tolist() == base[0][:2]  # prefix undisturbed
        # Accept only row 0 (reject the tail), rewind to length 5, and
        # continue plain: the stream must rejoin the baseline exactly.
        cont, _, _ = plain(
            cks2, cvs2, np.array([5, 3, 0], np.int32),
            np.array([base[0][0], base[1][0], 0], np.int32), 3,
        )
        assert cont[0] == base[0][1:4]
        assert cont[1] == base[1][1:4]

    @pytest.mark.parametrize("q_n", [1, 3])
    @pytest.mark.parametrize("kernel,interpret", [
        ("gather", False), ("paged", True),
    ])
    def test_q_pad_rows_change_nothing(self, kernel, interpret, q_n):
        """The chip's step (`q_pad=8`: padded query rows, and on the
        gather path a fresh tail padded to whole 128-key blocks) scores
        and writes what the unpadded step does."""
        model, params = shared_model()
        cfg = model.config
        rng = np.random.RandomState(0)
        shape = (cfg.n_layers, 9, 16, cfg.n_heads, cfg.head_dim)
        ck = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
        cv = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
        args = (
            params, jnp.asarray(rng.randint(1, 200, (3, q_n)), jnp.int32),
            jnp.asarray([37, 5, 0], jnp.int32),
            jnp.asarray([q_n, 1, 1], jnp.int32),
            jnp.asarray([True, True, False]), ck, cv,
            jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]], jnp.int32),
        )
        plain, padded = (
            model.decode_kv(
                *args, q_pad=q_pad, kernel=kernel, interpret=interpret)
            for q_pad in (1, 8)
        )
        # logits of the live slots, then the pool: the same rows written
        for a, b in zip((padded[0][:2],) + padded[1:],
                        (plain[0][:2],) + plain[1:]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


class TestSpeculationFault:
    def test_fault_degrades_to_plain_decode_counted(self):
        """Injected draft/verify failure: the iteration degrades to
        plain one-token decode, the fallback is counted, the engine
        survives, and streams stay bit-identical."""
        from determined_tpu.serving.engine import SPEC_FALLBACKS

        base = _baseline("gather", "off")
        before = SPEC_FALLBACKS.value
        plan = faults.FaultPlan(
            {"serving.speculation": faults.FaultSpec(failures=2)},
        )
        with faults.plan_active(plan):
            streams, stats = _run("gather", "off", {
                "mode": "ngram", "draft_len": 4, "min_match": 2,
            })
        assert streams == base
        assert stats["fallbacks"] == 2
        assert SPEC_FALLBACKS.value == before + 2
        # later iterations (past the injected failures) still speculated
        assert stats["proposed_tokens"] > 0


class TestSpeculationConfig:
    def test_valid_configs(self):
        ServingConfig.from_dict({"speculation": {"mode": "off"}})
        ServingConfig.from_dict({"speculation": {
            "mode": "ngram", "draft_len": 8, "min_match": 1,
        }})
        # the bench fixture model is servable by name (paired with
        # DTPU_SERVING_CHECKPOINT it serves the pre-trained weights)
        ServingConfig.from_dict({"model": "fixture"})

    def test_named_errors(self):
        with pytest.raises(ValueError, match="speculation.mode 'turbo'"):
            ServingConfig.from_dict({"speculation": {"mode": "turbo"}})
        for bad in (0, 9, "4", True):
            with pytest.raises(ValueError, match="draft_len"):
                ServingConfig.from_dict({"speculation": {
                    "mode": "ngram", "draft_len": bad,
                }})
        with pytest.raises(ValueError, match="min_match"):
            ServingConfig.from_dict({"speculation": {
                "mode": "ngram", "min_match": 0,
            }})
        with pytest.raises(ValueError, match="unknown key 'depth'"):
            ServingConfig.from_dict({"speculation": {"depth": 2}})
        with pytest.raises(ValueError, match="must be an object"):
            ServingConfig.from_dict({"speculation": "on"})

    def test_expconf_routes_speculation_errors(self):
        from determined_tpu.master import expconf

        errs = expconf.validate({
            "entrypoint": "x",
            "serving": {"speculation": {"mode": "ngram", "draft_len": 99}},
        })
        assert any("speculation.draft_len" in e for e in errs)
        assert not expconf.validate({
            "entrypoint": "x",
            "serving": {"speculation": {"mode": "ngram", "draft_len": 4}},
        })

    def test_kill_switch_and_force_env(self):
        with _env(DTPU_SPEC_DECODE="0"):
            eng = make_engine(speculation={
                "mode": "ngram", "draft_len": 4, "min_match": 2,
            })
            assert eng._spec_fn is None
            assert eng.stats()["speculation"]["mode"] == "off"
        with _env(DTPU_SPEC_DECODE="1"):
            eng = make_engine()
            assert eng._spec_fn is not None
            assert eng.stats()["speculation"]["mode"] == "ngram"

    def test_stats_surface(self):
        streams, stats = _run("gather", "off", {
            "mode": "ngram", "draft_len": 4, "min_match": 2,
        })
        assert set(stats) >= {
            "mode", "draft_len", "min_match", "proposed_tokens",
            "accepted_tokens", "rollback_tokens", "fallbacks",
            "acceptance_rate",
        }
        assert stats["proposed_tokens"] == (
            stats["accepted_tokens"] + stats["rollback_tokens"]
        )
        assert stats["acceptance_rate"] == pytest.approx(
            stats["accepted_tokens"] / stats["proposed_tokens"], abs=1e-4,
        )


class TestProposer:
    def test_basic_lookup_and_cap(self):
        assert propose_ngram_draft([1, 2, 3, 4, 1, 2], 4, 2) == [3, 4, 1, 2]
        assert propose_ngram_draft([1, 2, 3, 4, 1, 2], 2, 2) == [3, 4]

    def test_most_recent_occurrence_wins(self):
        assert propose_ngram_draft(
            [1, 2, 9, 1, 2, 7, 1, 2], 3, 2,
        ) == [7, 1, 2]

    def test_no_match_and_degenerate(self):
        assert propose_ngram_draft([1, 2, 3, 4, 5], 4, 2) == []
        assert propose_ngram_draft([1, 2], 4, 2) == []
        assert propose_ngram_draft([1, 2, 3], 4, 3) == []
        assert propose_ngram_draft([1, 2, 3], 0, 1) == []

    def test_terminal_gram_excluded(self):
        # the trailing gram itself must not match (it would propose the
        # tokens being predicted)
        assert propose_ngram_draft([5, 1, 5], 4, 1) == [1, 5]
        assert propose_ngram_draft([3, 3, 3, 3], 4, 2) == [3]

    def test_byte_alignment_no_false_match(self):
        # values whose int32 little-endian bytes create an UNALIGNED
        # byte-level hit: [0x01000000, 0x00000001] → bytes contain the
        # pattern of 0x00000100 at offset 2; an alignment-naive rfind
        # would propose from a token boundary that does not exist
        h = [0x01000000, 0x00000001, 0x00010000, 0x00000100]
        out = propose_ngram_draft(h, 4, 1)
        # whatever is proposed must come from a REAL token occurrence
        assert all(t in h for t in out)
