"""Iteration-level continuous-batching generation engine.

Orca-style scheduling on top of the repo's own primitives: ONE jitted
decode step runs over the whole active batch per iteration, and requests
join/leave the batch BETWEEN iterations without draining it —

- admission packs waiting prompts into a fixed-geometry prefill batch via
  ``batch_inference.pack_sequences`` (segment ids isolate prompts; the
  flash kernels mask within segments) and scatters each prompt's K/V into
  pages borrowed from the preallocated pool (kv_cache.PagePool);
- decode gathers each slot's pages and runs the flash kernel in the
  bottom-aligned ``kv_offset`` geometry with segment masking trimming the
  dead tail — every shape is static in (max_batch_size, page-table width,
  pool geometry), so batch composition changes never recompile;
- the SLO layer sheds at submit (queue bound, expired deadline, page-pool
  pressure → ``Shed`` with a Retry-After hint) and finishes in-flight
  requests the moment their deadline passes;
- every phase is observable: ``dtpu_serving_*`` metrics and per-request
  W3C trace spans (queue → prefill → decode) parented to the submitting
  client's traceparent.

With ``serving.prefix_cache: on`` the pool grows a third page state:
finished requests' full-token pages stay CACHED in a radix tree
(kv_cache.PrefixCache) instead of returning to the free list, admission
maps matched leading pages straight into new requests' page tables, and
only the tail is prefilled — through ``prefill_kv_cached``, which
attends the tail to the cached prefix K/V in the same bottom-aligned
``kv_offset`` geometry decode uses, so greedy streams are identical
cache-on vs cache-off.

With ``serving.speculation.mode: ngram`` greedy slots additionally
speculate: a prompt-lookup proposer drafts up to ``draft_len`` tokens
from the request's own token history, ONE compiled verify step scores
all ``draft_len + 1`` positions at the slot's bottom-aligned offsets
(plain and sampled slots ride the same step with ``q_lens = 1``), the
accepted prefix commits and the rejected tail rolls back by rewinding
``lengths`` — pages are pre-budgeted per request, so rollback never
touches the free list. Greedy streams are bit-identical spec-on vs
spec-off on both decode kernels.

Fault sites (common/faults.py): ``serving.admission`` (deterministic
shed), ``serving.decode`` (mid-stream failure — SSE error event, pages
freed), ``serving.page_alloc`` (pool exhaustion), ``serving.prefix_cache``
(poisoned lookup → counted fallback to a normal full prefill),
``serving.speculation`` (draft/verify failure → counted fallback to
plain one-token decode) — the chaos drills in tests/test_serving.py,
tests/test_prefix_cache.py and tests/test_speculation.py exercise all
five.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import math
import os
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from determined_tpu.batch_inference import pack_sequences
from determined_tpu.common import faults
from determined_tpu.common import trace as trace_mod
from determined_tpu.common.metrics import REGISTRY as METRICS
from determined_tpu.serving.config import ServingConfig
from determined_tpu.serving.kv_cache import (
    PagePool,
    PoolExhausted,
    PrefixCache,
)
from determined_tpu.serving.speculation import propose_ngram_draft

logger = logging.getLogger("determined_tpu.serving")

# -- observability plane (dtpu_serving_*) ------------------------------------
REQUESTS = METRICS.counter(
    "dtpu_serving_requests_total",
    "Generation requests by outcome (ok, shed, error, deadline).",
    labels=("outcome",),
)
SHED = METRICS.counter(
    "dtpu_serving_shed_total",
    "Requests shed by the admission layer, by reason.",
    labels=("reason",),
)
TOKENS = METRICS.counter(
    "dtpu_serving_tokens_total",
    "Tokens generated (streamed to clients).",
)
DECODE_ITERATIONS = METRICS.counter(
    "dtpu_serving_decode_iterations_total",
    "Iteration-level decode steps executed over the active batch.",
)
BATCH_JOINS = METRICS.counter(
    "dtpu_serving_batch_joins_total",
    "Requests admitted into an already-non-empty batch (the "
    "continuous-batching signature: late joiners never drain the batch).",
)
DECODE_FAILURES = METRICS.counter(
    "dtpu_serving_decode_failures_total",
    "Decode iterations lost to failure (injected or real); affected "
    "requests get an SSE error event and their pages return to the pool.",
)
QUEUE_DEPTH = METRICS.gauge(
    "dtpu_serving_queue_depth", "Requests waiting for admission.",
)
BATCH_OCCUPANCY = METRICS.gauge(
    "dtpu_serving_batch_occupancy", "Active decode-batch slots.",
)
KV_PAGES_READ = METRICS.counter(
    "dtpu_serving_kv_pages_read_total",
    "KV-cache pages decode iterations actually read. Paged kernel: live "
    "pages summed over active slots (dead page-table tails cost neither "
    "DMA nor compute). Gather fallback: the full page window every "
    "iteration — the contiguous-buffer round-trip the paged kernel "
    "removes; the two rates differ by exactly the win.",
)
SPEC_PROPOSED = METRICS.counter(
    "dtpu_serving_spec_proposed_tokens_total",
    "Draft tokens proposed by the prompt-lookup speculator (verify "
    "scores each; acceptance rate = accepted / proposed).",
)
SPEC_ACCEPTED = METRICS.counter(
    "dtpu_serving_spec_accepted_tokens_total",
    "Draft tokens the verify step accepted (each saved one decode "
    "iteration; the bonus token verify always emits is not counted).",
)
SPEC_ROLLBACK = METRICS.counter(
    "dtpu_serving_spec_rollback_tokens_total",
    "Draft tokens rejected and rolled back by rewinding lengths — pure "
    "host bookkeeping; pages are pre-budgeted so rollback never touches "
    "the free list.",
)
SPEC_FALLBACKS = METRICS.counter(
    "dtpu_serving_spec_fallbacks_total",
    "Decode iterations that degraded to plain one-token decode because "
    "the draft/verify path failed (injected or real); streams stay "
    "bit-identical, only the multi-token win is lost.",
)
DECODE_ITER_LATENCY = METRICS.histogram(
    "dtpu_serving_decode_iteration_seconds",
    "Decode-iteration wall latency by kernel path (paged = in-kernel "
    "page-table attention, gather = contiguous-K/V fallback) — the "
    "paged-vs-gather win, live on /metrics.",
    labels=("path",),
)
TTFT = METRICS.histogram(
    "dtpu_serving_ttft_seconds",
    "Submit-to-first-token latency (the serving SLO; p99 via buckets).",
)
E2E = METRICS.histogram(
    "dtpu_serving_e2e_seconds",
    "Submit-to-done latency of completed requests.",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             30.0, 60.0, 120.0),
)


def first_fit_layout(lens, seq_len, rows_cap):
    """(row, start) coordinates for docs of `lens` under pack_sequences'
    greedy first-fit over at most `rows_cap` rows of `seq_len`, or None
    when they don't fit ONE emitted batch. The engine's admission AND its
    prefill scatter both use this ONE mirror of the packing algorithm
    (pack_sequences builds the arrays; a runtime assert in _prefill keeps
    the two implementations honest)."""
    rows: List[int] = []
    layout: List[Tuple[int, int]] = []
    for ln in lens:
        for i, used in enumerate(rows):
            if used + ln <= seq_len:
                layout.append((i, used))
                rows[i] = used + ln
                break
        else:
            if len(rows) == rows_cap:
                return None
            layout.append((len(rows), 0))
            rows.append(ln)
    return layout


def _scatter_kv(cache_k, cache_v, k_l, v_l, src_idx, dst_pages):
    """Move a whole prefill batch's K/V into the paged pool in ONE
    in-place (donated) PAGE-GRANULAR update. k_l/v_l are [L, B, S, H, Dh]
    from prefill_kv; src_idx [P, page_size] holds flat token coordinates
    into the packed [B·S] batch per destination page, dst_pages [P] the
    pool page each lands on. Admission touches exactly the pages the
    admitted requests own (padding rows target scratch page 0, whose
    contents are never read live) — the page-identity invariant the
    in-kernel paged decode and future prefix caching rely on. Eager
    per-request ``.at[].set()`` would copy the full pool twice per
    admitted request; per-token scatter coordinates would write every
    non-prompt position of the packed batch into the scratch page."""
    n_layers, _, _, n_heads, head_dim = k_l.shape
    flat_k = k_l.reshape(n_layers, -1, n_heads, head_dim)
    flat_v = v_l.reshape(n_layers, -1, n_heads, head_dim)
    cache_k = cache_k.at[:, dst_pages].set(flat_k[:, src_idx])
    cache_v = cache_v.at[:, dst_pages].set(flat_v[:, src_idx])
    return cache_k, cache_v


class Shed(Exception):
    """Admission refused the request; retry after `retry_after` seconds."""

    def __init__(self, reason: str, retry_after: float) -> None:
        super().__init__(f"request shed: {reason}")
        self.reason = reason
        self.retry_after = retry_after


class PromptTooLong(ValueError):
    """The prompt (or prompt + max_new_tokens) exceeds what this replica's
    pool geometry / model context can ever hold — a client error (400),
    not a transient shed."""


@dataclasses.dataclass
class Request:
    request_id: str
    prompt: List[int]
    max_new_tokens: int
    deadline: float                     # absolute wall time
    temperature: float = 0.0
    trace: Optional[Tuple[str, str]] = None
    # -- engine-owned state --
    events: "queue_mod.Queue" = dataclasses.field(
        default_factory=queue_mod.Queue
    )
    tokens: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    #: prefix-cache hit state: the matched radix nodes (pinned for this
    #: request's lifetime) whose pages head `pages`.
    cached_nodes: List[Any] = dataclasses.field(default_factory=list)
    cached_pages: int = 0
    slot: int = -1
    length: int = 0                     # tokens in cache
    last_token: int = 0
    finish_reason: str = ""
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0

    def stream(
        self, timeout: Optional[float] = None
    ) -> Iterator[Tuple[str, Any]]:
        """Yield ("token", id) events then exactly one terminal
        ("done", info) or ("error", message) event. The default timeout
        derives from the REQUEST's deadline (+ slack for the terminal
        event) — a fixed constant would cut off generations whose
        configured deadline legitimately runs longer."""
        if timeout is None:
            timeout = max(30.0, self.deadline - time.time() + 30.0)
        deadline = time.time() + timeout
        while True:
            remaining = deadline - time.time()
            if remaining <= 0:
                yield ("error", "client stream timeout")
                return
            try:
                kind, payload = self.events.get(timeout=min(remaining, 1.0))
            except queue_mod.Empty:
                continue
            yield (kind, payload)
            if kind in ("done", "error"):
                return

    def result(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        """Drain the stream and return the final summary (non-SSE mode)."""
        toks: List[int] = []
        for kind, payload in self.stream(timeout=timeout):
            if kind == "token":
                toks.append(payload)
            elif kind == "done":
                return {"tokens": toks, **payload}
            else:
                return {"tokens": toks, "error": payload}
        return {"tokens": toks, "error": "stream ended unexpectedly"}


class GenerationEngine:
    """Continuous-batching engine over one model replica.

    Thread model: HTTP handler threads call submit(); ONE engine thread
    owns all device state (caches, jitted calls) and drives admission →
    prefill → decode iterations. Per-request event queues carry tokens
    back to the handler threads.
    """

    def __init__(self, model, params, config: ServingConfig) -> None:
        import jax
        import jax.numpy as jnp

        # Deferred like every jax import in this module: serving.engine
        # is imported by master-side processes that never run a kernel.
        from determined_tpu.ops.paged_attention import LANE_GRANULE

        self.model = model
        self.params = params
        self.cfg = config
        c = model.config
        if config.prefill_seq > c.seq_len:
            raise ValueError(
                f"serving.prefill_seq ({config.prefill_seq}) exceeds the "
                f"model context ({c.seq_len})"
            )
        self.max_total = min(c.seq_len, config.max_context)
        self.pool = PagePool(config.num_pages)
        self._jnp = jnp
        self.cache_k = jnp.zeros(
            (c.n_layers, config.num_pages, config.page_size,
             c.n_heads, c.head_dim), c.dtype,
        )
        self.cache_v = jnp.zeros_like(self.cache_k)
        #: the replica's device (stats() reads its memory high-water mark)
        self._device = next(iter(self.cache_k.devices()))
        #: decode query-row padding: lane-friendly on TPU, minimal on CPU
        #: (the blockwise reference pays per padded row; the MXU doesn't).
        self._q_pad = 8 if jax.default_backend() == "tpu" else 1
        self._prefill_fn = jax.jit(model.prefill_kv)
        self._scatter_fn = jax.jit(_scatter_kv, donate_argnums=(0, 1))
        # -- prefix cache (serving.prefix_cache: on) ---------------------
        # off reproduces the return-to-free-list lifecycle exactly; on
        # layers the radix cache over the SAME pool (eviction hooks into
        # alloc) and compiles the prefix-aware tail prefill once.
        self.prefix_cache: Optional[PrefixCache] = None
        self._prefill_cached_fn = None
        if config.prefix_cache == "on":
            self.prefix_cache = PrefixCache(self.pool, config.page_size)
            self._prefill_cached_fn = jax.jit(self._prefill_cached_step)
        #: static page-granular prefill budget: every admitted doc spans
        #: ceil(len/page_size) ≤ tokens/page_size + 1 pages, so one packed
        #: batch touches at most rows·seq/page_size + docs pages (docs ≤
        #: batch slots). Padding entries write scratch page 0.
        self._prefill_pages_max = (
            config.prefill_rows
            * math.ceil(config.prefill_seq / config.page_size)
            + config.max_batch_size
        )
        # -- decode kernel resolution (done ONCE, outside jit) -----------
        # serving.decode_kernel: auto → paged on TPU, gather elsewhere;
        # paged → paged on TPU, gather off-TPU (the CPU backend always
        # auto-selects gather); gather → gather. DTPU_PAGED_ATTN
        # overrides: 0 = kill switch back to the PR-6 gather behavior,
        # 1 = force paged (Pallas interpret mode off-TPU — the CPU
        # parity/test hook).
        on_tpu = jax.default_backend() == "tpu"
        env = os.environ.get("DTPU_PAGED_ATTN", "")
        if env == "0":
            self._decode_kernel = "gather"
        elif env == "1":
            self._decode_kernel = "paged"
        elif config.decode_kernel == "gather":
            self._decode_kernel = "gather"
        else:  # "auto" and "paged" both follow the backend
            self._decode_kernel = "paged" if on_tpu else "gather"
            if config.decode_kernel == "paged" and not on_tpu:
                logger.info(
                    "serving.decode_kernel=paged on a %s backend: "
                    "auto-selecting the gather fallback (DTPU_PAGED_ATTN=1 "
                    "forces the paged kernel in interpret mode)",
                    jax.default_backend(),
                )
        self._paged_interpret = self._decode_kernel == "paged" and not on_tpu
        if (
            self._decode_kernel == "paged"
            and not self._paged_interpret
            and config.page_size % LANE_GRANULE
        ):
            # Config validation names this for an EXPLICIT `paged`; an
            # `auto` (or env-forced) resolution onto a misaligned pool
            # must degrade to the gather fallback, not crash-loop the
            # replica at its first decode iteration.
            logger.warning(
                "serving: page_size %d is not a multiple of the %d lane "
                "granule; paged decode kernel unavailable — falling back "
                "to the gather path",
                config.page_size, LANE_GRANULE,
            )
            self._decode_kernel = "gather"
        self._paged_block_h = None
        if self._decode_kernel == "paged":
            from determined_tpu.ops.flash_autotune import tune_paged_block_h

            # Heads-per-step sizing comes from the autotuner (pool
            # geometry in its cache key), never a literal at a call site.
            self._paged_block_h = tune_paged_block_h(
                n_heads=c.n_heads, head_dim=c.head_dim,
                page_size=config.page_size, num_pages=config.num_pages,
                pages_per_slot=config.max_pages_per_request,
                batch=config.max_batch_size, q_rows=self._q_pad,
                dtype=c.dtype,
            )
        self._decode_fn = jax.jit(
            functools.partial(
                self._decode_step, q_pad=self._q_pad,
                kernel=self._decode_kernel, block_h=self._paged_block_h,
                interpret=self._paged_interpret,
            ),
            donate_argnums=(4, 5),
        )
        # -- speculative decoding resolution (done ONCE, outside jit) ----
        # serving.speculation.mode, with DTPU_SPEC_DECODE overriding at
        # engine build: 0 = kill switch back to one-token decode,
        # 1 = force the ngram proposer. When on, ONE spec decode step is
        # compiled with static Q = draft_len + 1 query rows; plain and
        # speculating slots share it (plain slots ride with q_lens = 1),
        # so mixed batches never recompile.
        env_spec = os.environ.get("DTPU_SPEC_DECODE", "")
        if env_spec == "0":
            self._spec_mode = "off"
        elif env_spec == "1":
            self._spec_mode = "ngram"
        else:
            self._spec_mode = config.spec_mode
        self._spec_draft_len = config.spec_draft_len
        self._spec_min_match = config.spec_min_match
        self._spec_fn = None
        if self._spec_mode == "ngram":
            q_spec = self._spec_draft_len + 1
            qp_spec = -(-q_spec // self._q_pad) * self._q_pad
            spec_block_h = self._paged_block_h
            if self._decode_kernel == "paged":
                from determined_tpu.ops.flash_autotune import (
                    tune_paged_block_h,
                )

                # The verify step runs the paged kernel at qp_spec query
                # rows, a different tile than the one-token step — tuned
                # separately under its own cache key.
                spec_block_h = tune_paged_block_h(
                    n_heads=c.n_heads, head_dim=c.head_dim,
                    page_size=config.page_size, num_pages=config.num_pages,
                    pages_per_slot=config.max_pages_per_request,
                    batch=config.max_batch_size, q_rows=qp_spec,
                    dtype=c.dtype,
                )
            self._spec_fn = jax.jit(
                functools.partial(
                    self._spec_decode_step, q_pad=self._q_pad,
                    kernel=self._decode_kernel, block_h=spec_block_h,
                    interpret=self._paged_interpret,
                ),
                donate_argnums=(5, 6),
            )
        self._queue: deque = deque()
        self._slots: List[Optional[Request]] = [None] * config.max_batch_size
        self._lock = threading.Lock()
        # Stats counters get their own lock: _count_shed fires from paths
        # that may already hold the queue lock (submit's bounded-queue
        # check), and threading.Lock is not reentrant.
        self._stats_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._rng = np.random.default_rng(0)
        self._counter = 0
        self._iter_count = 0
        self._done_count = 0
        self._shed_count = 0
        self._tokens_emitted = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rollback = 0
        self._spec_fallbacks = 0
        self._decode_backend = (
            "pallas" if on_tpu
            else ("interpret" if self._paged_interpret else "reference")
        )

    # -- jitted decode ------------------------------------------------------
    def _decode_step(self, params, last, lengths, active, ck, cv, pt,
                     temps, key, *, q_pad, kernel="gather", block_h=None,
                     interpret=False):
        import jax
        import jax.numpy as jnp

        # The verify step at Q = 1: every slot brings its one token.
        logits, ck, cv = self.model.decode_kv(
            params, last[:, None], lengths, jnp.ones_like(lengths), active,
            ck, cv, pt, q_pad=q_pad, kernel=kernel, block_h=block_h,
            interpret=interpret,
        )
        logits = logits[:, 0]
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temps, 1e-6)[:, None]
        )
        nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
        return nxt, ck, cv

    # -- jitted speculative decode ------------------------------------------
    def _spec_decode_step(self, params, toks, lengths, q_lens, active, ck,
                          cv, pt, temps, key, *, q_pad, kernel="gather",
                          block_h=None, interpret=False):
        """One verify-in-one-step iteration over the static batch. toks
        [B, Q] carries row 0 = the slot's last committed token and rows
        1..q_lens-1 = its draft; the verify scores all Q positions at the
        bottom-aligned offsets in ONE call (plain slots ride the same
        compiled step with q_lens = 1). Returns the sampled/greedy row-0
        token (the spec-off-identical next token) and the full greedy
        grid the host acceptance loop walks."""
        import jax
        import jax.numpy as jnp

        logits, ck, cv = self.model.decode_kv(
            params, toks, lengths, q_lens, active, ck, cv, pt,
            q_pad=q_pad, kernel=kernel, block_h=block_h,
            interpret=interpret,
        )
        greedy = jnp.argmax(logits, axis=-1)                  # [B, Q]
        sampled = jax.random.categorical(
            key, logits[:, 0] / jnp.maximum(temps, 1e-6)[:, None]
        )
        row0 = jnp.where(temps > 0, sampled, greedy[:, 0]).astype(jnp.int32)
        return row0, greedy.astype(jnp.int32), ck, cv

    # -- jitted cached-tail prefill -----------------------------------------
    def _prefill_cached_step(self, params, tokens, positions, segs, ck, cv,
                             prefix_pt, prefix_len):
        """Gather each row's cached prefix pages contiguous and run the
        prefix-aware tail prefill in ONE jitted call (the gathered buffer
        never round-trips to host). ck/cv are READ-ONLY here — the pages
        keep serving other requests; donation stays with the scatter."""
        import jax.numpy as jnp

        n_layers, _, _, h, hd = ck.shape
        b = tokens.shape[0]
        pk = ck[:, prefix_pt].reshape(n_layers, b, -1, h, hd)
        pv = cv[:, prefix_pt].reshape(n_layers, b, -1, h, hd)
        sp = pk.shape[2]
        prefix_seg = (
            jnp.arange(sp)[None, :] < prefix_len[:, None]
        ).astype(jnp.int32)
        return self.model.prefill_kv_cached(
            params, tokens, positions, segs, pk, pv, prefix_seg
        )

    # -- lifecycle ----------------------------------------------------------
    def warmup(self) -> None:
        """Compile every program the loop can reach — packed prefill, page
        scatter, cached-tail prefill, and the decode step (or the
        speculative verify step that replaces it) — by running each once
        on an all-padding / all-inactive batch, which writes scratch page
        0 only. A program the compiler refuses is not a transient fault:
        raising here, before the server accepts traffic, ends the process
        instead of erroring every request behind a healthy `/healthz`
        (`_recover` keeps its job for run-time faults)."""
        import jax

        cfg = self.cfg
        b, per_req = cfg.max_batch_size, cfg.max_pages_per_request

        def zeros(*shape, dtype=np.int32):
            return self._jnp.zeros(shape, dtype)

        grid = zeros(cfg.prefill_rows, cfg.prefill_seq)
        _, k_l, v_l = self._prefill_fn(self.params, grid, grid, grid)
        self.cache_k, self.cache_v = self._scatter_fn(
            self.cache_k, self.cache_v, k_l, v_l,
            zeros(self._prefill_pages_max, cfg.page_size),
            zeros(self._prefill_pages_max),
        )
        if self._prefill_cached_fn is not None:
            # Block: this reads the pool the decode step below donates.
            jax.block_until_ready(self._prefill_cached_fn(
                self.params, grid, grid, grid, self.cache_k, self.cache_v,
                zeros(cfg.prefill_rows, per_req), zeros(cfg.prefill_rows),
            ))
        tail = (
            zeros(b, dtype=bool), self.cache_k, self.cache_v,
            zeros(b, per_req), zeros(b, dtype=np.float32),
            jax.random.PRNGKey(0),
        )
        if self._spec_fn is not None:
            out = self._spec_fn(
                self.params, zeros(b, self._spec_draft_len + 1), zeros(b),
                self._jnp.ones((b,), np.int32), *tail,
            )
        else:
            out = self._decode_fn(self.params, zeros(b), zeros(b), *tail)
        self.cache_k, self.cache_v = jax.block_until_ready(out[-2:])

    def start(self) -> None:
        self.warmup()
        self._thread = threading.Thread(
            target=self._run, name="serving-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
            QUEUE_DEPTH.set(0)
        for req in pending:
            req.events.put(("error", "engine shutting down"))
        for i, req in enumerate(self._slots):
            if req is not None:
                self._slots[i] = None
                self._retire_pages(req, cacheable=False)
                req.events.put(("error", "engine shutting down"))
        BATCH_OCCUPANCY.set(0)

    # -- admission (SLO layer) ---------------------------------------------
    def submit(
        self,
        prompt: List[int],
        max_new_tokens: Optional[int] = None,
        deadline_s: Optional[float] = None,
        temperature: float = 0.0,
        trace: Optional[Tuple[str, str]] = None,
    ) -> Request:
        """Admit a request into the waiting queue, or refuse it.

        Raises PromptTooLong (client error — this replica can never serve
        it) or Shed (transient — queue full, expired deadline, injected
        admission fault; carries retry_after). Instrumented fault site:
        ``serving.admission``.
        """
        cfg = self.cfg
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise PromptTooLong("prompt must be a non-empty token list")
        explicit = bool(max_new_tokens)
        mnt = int(max_new_tokens) if explicit else cfg.max_new_tokens
        mnt = max(1, min(mnt, cfg.max_new_tokens))
        if len(prompt) > cfg.prefill_seq:
            raise PromptTooLong(
                f"prompt is {len(prompt)} tokens; this replica packs "
                f"prefills at {cfg.prefill_seq}"
            )
        if not explicit:
            # The config-default token budget is a CAP, not a promise:
            # clamp it to the remaining context so the documented defaults
            # (e.g. model=tiny whose seq_len is below max_new_tokens=256)
            # serve out of the box. An EXPLICIT ask that cannot fit is
            # still the client error below.
            mnt = max(1, min(mnt, self.max_total - len(prompt)))
        if len(prompt) + mnt > self.max_total:
            raise PromptTooLong(
                f"prompt + max_new_tokens = {len(prompt) + mnt} exceeds "
                f"the replica context ({self.max_total} = min(model "
                f"seq_len, {cfg.max_pages_per_request} pages × "
                f"{cfg.page_size}))"
            )
        try:
            faults.inject("serving.admission")
        except faults.InjectedFault:
            self._count_shed("fault")
            raise Shed("injected admission fault", cfg.shed_retry_after_s)
        now = time.time()
        deadline = now + float(deadline_s or cfg.default_deadline_s)
        if deadline <= now:
            self._count_shed("deadline")
            raise Shed("deadline already expired", cfg.shed_retry_after_s)
        with self._lock:
            if len(self._queue) >= cfg.max_queue_depth:
                self._count_shed("queue_full")
                raise Shed(
                    f"queue full ({cfg.max_queue_depth})",
                    cfg.shed_retry_after_s,
                )
            self._counter += 1
            req = Request(
                request_id=f"req-{self._counter}",
                prompt=prompt,
                max_new_tokens=mnt,
                deadline=deadline,
                temperature=float(temperature),
                # Trace identity is fixed at ADMISSION (traceless clients
                # get a fresh root here, not at span-emit time): the TTFT
                # exemplar recorded at prefill must name the same trace
                # the request's spans later export under.
                trace=trace or (trace_mod.new_trace_id(), None),
                t_submit=now,
            )
            self._queue.append(req)
            QUEUE_DEPTH.set(len(self._queue))
        self._wake.set()
        return req

    def _count_shed(self, reason: str) -> None:
        SHED.labels(reason).inc()
        REQUESTS.labels("shed").inc()
        with self._stats_lock:
            self._shed_count += 1

    # -- engine loop --------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                progressed = False
                for _ in range(max(1, self.cfg.max_prefills_per_iter)):
                    admitted = self._admit()
                    if not admitted:
                        break
                    self._prefill(admitted)
                    progressed = True
                if any(r is not None for r in self._slots):
                    self._decode_iter()
                    progressed = True
                if not progressed:
                    self._wake.wait(timeout=0.02)
                    self._wake.clear()
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.exception("serving engine iteration failed")
                self._recover()
                time.sleep(0.1)  # resilience-ok: crash-loop damper, not a remote retry

    def _recover(self) -> None:
        """A REAL (non-injected) prefill/decode failure must behave like
        the injected serving.decode drill: evict the in-flight requests,
        return their pages, and close their client streams with an error
        event. Without this the crash leaks slots+pages forever and the
        affected clients hang to their stream timeout."""
        import jax.numpy as jnp

        DECODE_FAILURES.inc()
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            self._slots[i] = None
            self._retire_pages(req, cacheable=False)
            req.finish_reason = "error"
            REQUESTS.labels("error").inc()
            req.events.put(
                ("error", "engine iteration failed; partial stream, "
                 "pages freed")
            )
        BATCH_OCCUPANCY.set(0)
        if self.prefix_cache is not None:
            # The crash may have been mid-write (and the donated-buffer
            # rebuild below zeroes the pool outright): every cached
            # page's contents are suspect, so the whole tree goes.
            self.prefix_cache.flush()
        if self.cache_k.is_deleted() or self.cache_v.is_deleted():
            # A jit that raises AFTER consuming its donated inputs leaves
            # the pool buffers invalidated; rebuild them — evicting
            # everyone above made the contents disposable.
            c = self.model.config
            self.cache_k = jnp.zeros(
                (c.n_layers, self.cfg.num_pages, self.cfg.page_size,
                 c.n_heads, c.head_dim), c.dtype,
            )
            self.cache_v = jnp.zeros_like(self.cache_k)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def _pack_fits(self, lens: List[int], new_len: int) -> bool:
        """True when `new_len` joins `lens` in ONE emitted prefill batch
        (the shared first_fit_layout mirror of pack_sequences)."""
        return first_fit_layout(
            lens + [new_len], self.cfg.prefill_seq, self.cfg.prefill_rows
        ) is not None

    def _admit(self) -> List[Request]:
        """Move queue heads into free slots for ONE prefill round.
        Stops at slot/pack/page capacity; expired deadlines shed here.

        With the prefix cache on, each head is first walked through the
        radix tree: a hit pins the matched pages (refs++ BEFORE the
        alloc, so the alloc's own eviction can never pull them out from
        under us), allocates only the tail's pages, and takes one row of
        the cached-tail prefill batch; misses pack into the classic
        full-prompt prefill exactly as before. An injected
        ``serving.prefix_cache`` fault (or a hash-collision verify
        failure inside match) downgrades the head to a counted
        full-prefill fallback — never a corrupted stream."""
        admitted: List[Request] = []
        miss_lens: List[int] = []
        hit_rows = 0
        occupied_before = sum(1 for r in self._slots if r is not None)
        while True:
            with self._lock:
                if not self._queue:
                    break
                req = self._queue[0]
            free = self._free_slots()
            if len(free) <= len(admitted):
                break
            if time.time() > req.deadline:
                with self._lock:
                    self._queue.popleft()
                    QUEUE_DEPTH.set(len(self._queue))
                self._count_shed("deadline")
                req.events.put(("error", "deadline expired in queue"))
                continue
            nodes: List[Any] = []
            if self.prefix_cache is not None:
                try:
                    faults.inject("serving.prefix_cache")
                    nodes = self.prefix_cache.match(req.prompt)
                except faults.InjectedFault:
                    self.prefix_cache.note_fallback()
                    nodes = []
            if nodes:
                if hit_rows >= self.cfg.prefill_rows:
                    break  # cached-tail batch full; next iteration
            elif not self._pack_fits(miss_lens, len(req.prompt)):
                break
            need = self.pool.pages_for(
                len(req.prompt) + req.max_new_tokens, self.cfg.page_size
            )
            if nodes:
                self.prefix_cache.acquire(nodes)
            try:
                # The hit span needs no pages of its own (max_new >= 1
                # and match stops short of the full prompt, so at least
                # one fresh page is always needed — decode never writes
                # into a shared cached page).
                fresh = self.pool.alloc(need - len(nodes))
            except PoolExhausted:
                if nodes:
                    self.prefix_cache.release(nodes)
                if not admitted and occupied_before == 0:
                    # Nothing in flight will ever free pages: shed rather
                    # than wedge the queue head forever (the fault-driven
                    # exhaustion drill lands here deterministically).
                    with self._lock:
                        self._queue.popleft()
                        QUEUE_DEPTH.set(len(self._queue))
                    self._count_shed("pages")
                    req.events.put(
                        ("error", "page pool exhausted; retry later")
                    )
                    continue
                break  # pages free when an in-flight request finishes
            with self._lock:
                self._queue.popleft()
                QUEUE_DEPTH.set(len(self._queue))
            req.cached_nodes = nodes
            req.cached_pages = len(nodes)
            req.pages = [n.page for n in nodes] + fresh
            if self.prefix_cache is not None:
                if nodes:
                    self.prefix_cache.note_hit(len(nodes))
                    hit_rows += 1
                else:
                    self.prefix_cache.note_miss()
                    miss_lens.append(len(req.prompt))
            else:
                miss_lens.append(len(req.prompt))
            req.t_admit = time.time()
            slot = free[len(admitted)]
            req.slot = slot
            self._slots[slot] = req
            admitted.append(req)
            if occupied_before > 0:
                BATCH_JOINS.inc()
        return admitted

    # -- prefill ------------------------------------------------------------
    def _prefill(self, reqs: List[Request]) -> None:
        """One admission round's prefills: cache misses go through the
        classic packed full-prompt prefill, cache hits through the
        prefix-aware tail prefill (one row per request — every row has
        its own cached prefix, so rows cannot pack)."""
        misses = [r for r in reqs if not r.cached_pages]
        hits = [r for r in reqs if r.cached_pages]
        if misses:
            self._prefill_packed(misses)
        if hits:
            self._prefill_cached(hits)

    def _prefill_packed(self, reqs: List[Request]) -> None:
        import jax.numpy as jnp

        cfg = self.cfg
        # The ONE shared mirror of pack_sequences' first-fit gives each
        # request its (row, start) coordinates; pack_sequences builds the
        # actual arrays, and the layout-drift assert below keeps the
        # mirror honest against it.
        layout = first_fit_layout(
            [len(r.prompt) for r in reqs], cfg.prefill_seq, cfg.prefill_rows
        )
        assert layout is not None, "admission sized the pack to one batch"
        batches = list(pack_sequences(
            [r.prompt for r in reqs], cfg.prefill_seq, cfg.prefill_rows,
            overflow="error",
        ))
        assert len(batches) == 1, "admission sized the pack to one batch"
        batch = batches[0]
        tokens = batch["tokens"]
        segs = batch["segment_ids"]
        # Per-token position within its own document, plus PAGE-GRANULAR
        # scatter coordinates: one (source token window, destination
        # page) pair per pool page the admitted prompts own. A partial
        # last page clamps its source tail onto the doc's final token —
        # those dest positions sit past the slot's live length and are
        # masked by both decode kernels. Unused entries (src 0 → dst
        # scratch page 0) keep the shapes static.
        ps = cfg.page_size
        seq = tokens.shape[1]
        positions = np.zeros_like(tokens)
        src_idx = np.zeros((self._prefill_pages_max, ps), np.int32)
        dst_pages = np.zeros((self._prefill_pages_max,), np.int32)
        slot_i = 0
        for (row, start), req in zip(layout, reqs):
            ln = len(req.prompt)
            positions[row, start:start + ln] = np.arange(ln)
            assert tokens[row, start] == req.prompt[0], "pack layout drift"
            for pi in range(-(-ln // ps)):
                idx = start + pi * ps + np.arange(ps)
                src_idx[slot_i] = row * seq + np.minimum(idx, start + ln - 1)
                dst_pages[slot_i] = req.pages[pi]
                slot_i += 1
        assert slot_i <= self._prefill_pages_max, "prefill page budget"
        logits, k_l, v_l = self._prefill_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(segs),
        )
        self.cache_k, self.cache_v = self._scatter_fn(
            self.cache_k, self.cache_v, k_l, v_l,
            jnp.asarray(src_idx), jnp.asarray(dst_pages),
        )
        logits = np.asarray(logits, np.float32)
        now = time.time()
        for (row, start), req in zip(layout, reqs):
            ln = len(req.prompt)
            req.length = ln
            self._emit_first(req, logits[row, start + ln - 1], now)
        BATCH_OCCUPANCY.set(sum(1 for r in self._slots if r is not None))

    def _prefill_cached(self, reqs: List[Request]) -> None:
        """Prefix-cache hit path: prefill ONLY each request's tail (the
        tokens past its matched pages), attending through the cached
        prefix K/V gathered from the pool. Zero prefill compute and zero
        K/V writes for the hit span — the tail's K/V scatters into the
        request's fresh pages exactly like the packed path, and both
        decode kernels then read the mixed cached/fresh page table
        unchanged."""
        import jax.numpy as jnp

        cfg = self.cfg
        ps = cfg.page_size
        rows, seq = cfg.prefill_rows, cfg.prefill_seq
        tokens = np.zeros((rows, seq), np.int32)
        positions = np.zeros((rows, seq), np.int32)
        segs = np.zeros((rows, seq), np.int32)
        prefix_pt = np.zeros((rows, cfg.max_pages_per_request), np.int32)
        prefix_len = np.zeros((rows,), np.int32)
        src_idx = np.zeros((self._prefill_pages_max, ps), np.int32)
        dst_pages = np.zeros((self._prefill_pages_max,), np.int32)
        slot_i = 0
        for row, req in enumerate(reqs):
            m = req.cached_pages
            cached = m * ps
            tail = req.prompt[cached:]
            ln = len(tail)
            assert ln >= 1, "match always leaves a tail token to prefill"
            tokens[row, :ln] = tail
            # Absolute positions: the pos_embed index must match what a
            # full prefill would have used for these tokens.
            positions[row, :ln] = cached + np.arange(ln)
            segs[row, :ln] = 1
            prefix_pt[row, :m] = req.pages[:m]
            prefix_len[row] = cached
            # The tail starts ON a page boundary, so its pages align
            # with the scatter granule like any packed doc's.
            for pi in range(-(-ln // ps)):
                idx = pi * ps + np.arange(ps)
                src_idx[slot_i] = row * seq + np.minimum(idx, ln - 1)
                dst_pages[slot_i] = req.pages[m + pi]
                slot_i += 1
        assert slot_i <= self._prefill_pages_max, "prefill page budget"
        logits, k_l, v_l = self._prefill_cached_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(segs), self.cache_k, self.cache_v,
            jnp.asarray(prefix_pt), jnp.asarray(prefix_len),
        )
        # Block BEFORE the scatter dispatch: the scatter donates the pool
        # buffers this computation is still reading.
        logits = np.asarray(logits, np.float32)
        self.cache_k, self.cache_v = self._scatter_fn(
            self.cache_k, self.cache_v, k_l, v_l,
            jnp.asarray(src_idx), jnp.asarray(dst_pages),
        )
        now = time.time()
        for row, req in enumerate(reqs):
            ln = len(req.prompt) - req.cached_pages * ps
            req.length = len(req.prompt)
            self._emit_first(req, logits[row, ln - 1], now)
        BATCH_OCCUPANCY.set(sum(1 for r in self._slots if r is not None))

    def _emit_first(self, req: Request, logits_row: np.ndarray,
                    now: float) -> None:
        """Sample and stream a request's first token from its prefill
        logits (shared by the packed and cached-tail paths)."""
        first = self._sample_host(logits_row, req)
        req.last_token = first
        req.tokens.append(first)
        req.t_first_token = now
        # Exemplar: the p99 TTFT answer links to this request's
        # trace — but only when the head-sample will actually ship
        # the request's spans (the decision is a pure function of
        # the trace id, so it's knowable here). A sampled-out trace
        # as an exemplar would 404 in `dtpu traces show`.
        TTFT.observe(
            now - req.t_submit,
            trace_id=(
                req.trace[0]
                if trace_mod._keep_span(req.trace[0], False, 0.0)
                else None
            ),
        )
        TOKENS.inc()
        with self._stats_lock:
            self._tokens_emitted += 1
        req.events.put(("token", first))
        # a 1-token request is complete at prefill
        if len(req.tokens) >= req.max_new_tokens or (
            self.cfg.eos_id >= 0 and first == self.cfg.eos_id
        ):
            self._finish(req, "length" if len(req.tokens)
                         >= req.max_new_tokens else "eos")

    def _sample_host(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(np.argmax(logits))
        z = logits / req.temperature
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self._rng.choice(len(p), p=p))

    # -- decode -------------------------------------------------------------
    def _decode_iter(self) -> None:
        import jax
        import jax.numpy as jnp

        from determined_tpu.ops.paged_attention import paged_pages_read

        cfg = self.cfg
        try:
            faults.inject("serving.decode")
        except faults.InjectedFault:
            DECODE_FAILURES.inc()
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                self._slots[i] = None
                self._retire_pages(req, cacheable=False)
                req.finish_reason = "error"
                REQUESTS.labels("error").inc()
                req.events.put(
                    ("error", "decode step failed; partial stream, "
                     "pages freed")
                )
            BATCH_OCCUPANCY.set(0)
            return
        b = cfg.max_batch_size
        spec_on = self._spec_fn is not None
        last = np.zeros((b,), np.int32)
        lengths = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        temps = np.zeros((b,), np.float32)
        pt = np.zeros((b, cfg.max_pages_per_request), np.int32)
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            last[i] = req.last_token
            lengths[i] = req.length
            active[i] = True
            temps[i] = req.temperature
            pt[i, : len(req.pages)] = req.pages
        # -- draft proposal (host, per greedy slot) ----------------------
        # Every slot rides the same compiled step; plain / sampled /
        # draft-less slots simply keep q_lens = 1. The draft cap keeps
        # every written position inside the request's pre-budgeted pages
        # (rollback is then pure lengths bookkeeping), and an injected
        # `serving.speculation` fault degrades the WHOLE iteration to
        # one-token decode — streams stay bit-identical, only the
        # multi-token win is lost.
        drafts: List[List[int]] = [[] for _ in range(b)]
        q_lens = np.ones((b,), np.int32)
        if spec_on:
            try:
                faults.inject("serving.speculation")
                for i, req in enumerate(self._slots):
                    if req is None or req.temperature > 0:
                        continue
                    m_cap = min(
                        self._spec_draft_len,
                        req.max_new_tokens - len(req.tokens) - 1,
                        self.max_total - 2 - req.length,
                    )
                    if m_cap < 1:
                        continue
                    drafts[i] = propose_ngram_draft(
                        req.prompt + req.tokens, m_cap,
                        self._spec_min_match,
                    )
            except faults.InjectedFault:
                SPEC_FALLBACKS.inc()
                with self._stats_lock:
                    self._spec_fallbacks += 1
                drafts = [[] for _ in range(b)]
        self._iter_count += 1
        key = jax.random.PRNGKey(self._iter_count)
        t_iter = time.monotonic()
        greedy = None
        if spec_on:
            toks = np.zeros((b, self._spec_draft_len + 1), np.int32)
            toks[:, 0] = last
            for i, d in enumerate(drafts):
                if d:
                    toks[i, 1:1 + len(d)] = d
                    q_lens[i] = 1 + len(d)
            nxt, greedy, self.cache_k, self.cache_v = self._spec_fn(
                self.params, jnp.asarray(toks), jnp.asarray(lengths),
                jnp.asarray(q_lens), jnp.asarray(active), self.cache_k,
                self.cache_v, jnp.asarray(pt), jnp.asarray(temps), key,
            )
            greedy = np.asarray(greedy)
        else:
            nxt, self.cache_k, self.cache_v = self._decode_fn(
                self.params, jnp.asarray(last), jnp.asarray(lengths),
                jnp.asarray(active), self.cache_k, self.cache_v,
                jnp.asarray(pt), jnp.asarray(temps), key,
            )
        nxt = np.asarray(nxt)  # blocks until the device step is done
        DECODE_ITER_LATENCY.labels(self._decode_kernel).observe(
            time.monotonic() - t_iter
        )
        # Pages this iteration actually read. Paged: the host mirror of
        # the kernel's liveness predicate (dead page-table tails are
        # free; draft rows extend liveness by q_lens - 1 positions).
        # Gather: the full window materializes every iteration — the
        # counter rates differ by exactly the round-trip the paged
        # kernel removes.
        if self._decode_kernel == "paged":
            KV_PAGES_READ.inc(
                paged_pages_read(
                    lengths, active, cfg.page_size,
                    q_lens=q_lens if spec_on else None,
                )
            )
        else:
            KV_PAGES_READ.inc(len(lengths) * cfg.max_pages_per_request)
        DECODE_ITERATIONS.inc()
        now = time.time()
        for i, req in enumerate(list(self._slots)):
            if req is None:
                continue
            m = len(drafts[i])
            if m:
                # Verify row r scored position lengths + r + 1; walk the
                # accepted prefix (draft token r == greedy row r-1's
                # prediction) and emit greedy rows 0..n — the EXACT
                # tokens n+1 plain iterations would have produced. The
                # rejected tail rolls back by simply not advancing
                # req.length past the accepted span: its K/V sits beyond
                # every kernel's length mask and is overwritten before
                # it can ever become visible.
                g = greedy[i]
                n = 0
                while n < m and drafts[i][n] == int(g[n]):
                    n += 1
                emitted = [int(g[r]) for r in range(n + 1)]
                SPEC_PROPOSED.inc(m)
                SPEC_ACCEPTED.inc(n)
                SPEC_ROLLBACK.inc(m - n)
                with self._stats_lock:
                    self._spec_proposed += m
                    self._spec_accepted += n
                    self._spec_rollback += m - n
            else:
                emitted = [int(nxt[i])]
            for tok in emitted:
                req.length += 1      # the processed token entered the cache
                req.last_token = tok
                req.tokens.append(tok)
                TOKENS.inc()
                with self._stats_lock:
                    self._tokens_emitted += 1
                req.events.put(("token", tok))
                if cfg.eos_id >= 0 and tok == cfg.eos_id:
                    self._finish(req, "eos")
                    break
                elif len(req.tokens) >= req.max_new_tokens:
                    self._finish(req, "length")
                    break
                elif req.length + 1 >= self.max_total:
                    self._finish(req, "length")
                    break
                elif now > req.deadline:
                    self._finish(req, "deadline")
                    break
        BATCH_OCCUPANCY.set(sum(1 for r in self._slots if r is not None))

    def _retire_pages(self, req: Request, cacheable: bool) -> None:
        """Return a request's pages. Cache off: straight to the free
        list. Cache on: release the request's pins and (on clean
        completion) adopt its full K/V-written pages into the radix tree
        — the LRU-evictable cached state — freeing only the partial tail
        and unused reservation. Error paths free everything the request
        owned (the contents are suspect and must not be served)."""
        if req.pages:
            if self.prefix_cache is None:
                self.pool.free(req.pages)
            else:
                written = (req.prompt + req.tokens)[:req.length]
                self.prefix_cache.finish(
                    written, req.pages, req.cached_nodes, cacheable
                )
        req.pages = []
        req.cached_nodes = []
        req.cached_pages = 0

    def _finish(self, req: Request, reason: str) -> None:
        """Request leaves the batch between iterations: pages return to
        the pool (or the prefix cache) immediately — an early finisher
        frees capacity while its batch-mates keep decoding — spans and
        counters are emitted, and the terminal event closes the client
        stream."""
        self._slots[req.slot] = None
        # Every _finish reason (length/eos/deadline) leaves valid K/V in
        # the pages — a deadline cut is an SLO decision, not corruption.
        self._retire_pages(req, cacheable=True)
        req.finish_reason = reason
        req.t_done = time.time()
        outcome = "ok" if reason in ("length", "eos") else reason
        REQUESTS.labels(outcome).inc()
        # error/head-sampled requests ship their spans (tail policy), so
        # their trace ids are safe exemplars; head-sampled-out healthy
        # ones would dangle.
        e2e_linkable = reason not in ("length", "eos") or trace_mod._keep_span(
            req.trace[0], False, 0.0
        )
        E2E.observe(
            req.t_done - req.t_submit,
            trace_id=req.trace[0] if e2e_linkable else None,
        )
        with self._stats_lock:
            self._done_count += 1
        self._emit_spans(req)
        req.events.put(("done", {
            "reason": reason,
            "request_id": req.request_id,
            "prompt_tokens": len(req.prompt),
            "generated": len(req.tokens),
            "ttft_ms": round((req.t_first_token - req.t_submit) * 1e3, 3),
            "total_ms": round((req.t_done - req.t_submit) * 1e3, 3),
        }))

    def _emit_spans(self, req: Request) -> None:
        """Per-request W3C spans: submit → queue → prefill → first token →
        done, parented to the submitting client's traceparent."""
        # trace identity fixed at admission (submit); parent span id is
        # None for traceless clients — the request span roots the trace.
        trace_id, parent = req.trace
        root = trace_mod.new_span_id()
        trace_mod.export_span(
            "serving.request", trace_id=trace_id, span_id=root,
            parent_span_id=parent, start=req.t_submit, end=req.t_done,
            attributes={
                "serving.request_id": req.request_id,
                "serving.reason": req.finish_reason,
                "serving.prompt_tokens": len(req.prompt),
                "serving.generated": len(req.tokens),
            },
            error=req.finish_reason not in ("length", "eos"),
        )
        for name, start, end in (
            ("serving.queue", req.t_submit, req.t_admit),
            ("serving.prefill", req.t_admit, req.t_first_token),
            ("serving.decode", req.t_first_token, req.t_done),
        ):
            if end >= start > 0:
                trace_mod.export_span(
                    name, trace_id=trace_id, span_id=trace_mod.new_span_id(),
                    parent_span_id=root, start=start, end=end,
                )

    # -- introspection ------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            queued = len(self._queue)
        with self._stats_lock:
            done = self._done_count
            shed = self._shed_count
            emitted = self._tokens_emitted
            spec_proposed = self._spec_proposed
            spec_accepted = self._spec_accepted
            spec_rollback = self._spec_rollback
            spec_fallbacks = self._spec_fallbacks
        out = {
            "queued": queued,
            "active": sum(1 for r in self._slots if r is not None),
            "done": done,
            "shed": shed,
            "tokens_emitted": emitted,
            "pages_in_use": self.pool.pages_in_use,
            "pages_free": self.pool.free_pages,
            "decode_backend": self._decode_backend,
            "decode_kernel": self._decode_kernel,
            # None where the backend keeps no memory statistics (CPU).
            "device_peak_bytes": (self._device.memory_stats() or {}).get(
                "peak_bytes_in_use"
            ),
            "max_batch_size": self.cfg.max_batch_size,
            "max_context": self.max_total,
            "cache_hit_rate": 0.0,
            "speculation": {
                "mode": self._spec_mode,
                "draft_len": self._spec_draft_len,
                "min_match": self._spec_min_match,
                "proposed_tokens": spec_proposed,
                "accepted_tokens": spec_accepted,
                "rollback_tokens": spec_rollback,
                "fallbacks": spec_fallbacks,
                "acceptance_rate": (
                    round(spec_accepted / spec_proposed, 4)
                    if spec_proposed else 0.0
                ),
            },
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
            out["cache_hit_rate"] = round(self.prefix_cache.hit_rate, 4)
        return out
