"""Driver of `"kind": "serve"` mixes: a `GenerationEngine` started as
`serving/service.py::main` starts it, and clients that do what its HTTP
handlers do: `engine.submit(...)`, then read `Request.stream()`.

The model is built from the configuration file and handed to
`GenerationEngine(model, params, ServingConfig.from_dict(...))`, the
tail of `service.build_engine` (whose `serving.model` can name only
tiny|small|medium|fixture, so GPT-2 XL cannot go through it). Weights
come from `--seed` in one jitted call on the device.

Open loop (`"loop": "open"`): one dispatcher thread submits each
request when it is due (schedule from `benchmark/loadgen.py`) and hands
the stream to a reader thread; a pre-roll of the same mix runs before
the window so that it opens on a busy system. Closed loop: `clients`
threads, each sending its next request when the last one finished; the
window opens `preroll_seconds` after they start and the engine is
stopped when it closes. Times are the clients' own (`perf_counter` when
the token event left the stream), taken from the due time in the open
loop (`common/loadharness.py`'s rule).

Traffic file: see `benchmark/README.md`.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List

import numpy as np

#: program counters whose deltas over the window the readers use
COUNTERS = {
    "dtpu_serving_tokens_total": "tokens",
    "dtpu_serving_decode_iterations_total": "decode_iterations",
    "dtpu_serving_kv_pages_read_total": "kv_pages_read",
    "dtpu_serving_shed_total": "shed",
    "dtpu_serving_decode_iteration_seconds_sum": "decode_iter_seconds",
    "dtpu_serving_decode_iteration_seconds_count": "decode_iter_count",
}


class Served:
    """One request as its client saw it."""

    __slots__ = ("index", "due", "submitted", "prompt", "max_new",
                 "token_times", "tokens", "outcome", "request", "in_window")

    def __init__(self, index: int, due: float, prompt: List[int],
                 max_new: int, in_window: bool) -> None:
        self.index = index
        self.due = due                  # perf_counter instant
        self.submitted = 0.0
        self.prompt = prompt
        self.max_new = max_new
        self.token_times: List[float] = []
        self.tokens: List[int] = []
        self.outcome = "pending"        # ok | shed | error | cut | pending
        self.request = None             # the engine's Request (its stamps)
        self.in_window = in_window


def build_engine(h):
    import jax

    from benchmark.model import gpt_config_kwargs
    from determined_tpu.models.gpt import GPT, GPTConfig
    from determined_tpu.serving.config import ServingConfig
    from determined_tpu.serving.engine import GenerationEngine

    model = GPT(GPTConfig(**gpt_config_kwargs(h.config)))
    with h.span("engine.init_params"):
        params = jax.block_until_ready(
            jax.jit(model.init)(jax.random.PRNGKey(h.seed)))
    with h.span("engine.build"):
        engine = GenerationEngine(
            model, params, ServingConfig.from_dict(h.traffic["serving"]))
    with h.span("engine.start"):
        engine.start()      # compiles prefill, scatter, decode
    return engine, params


def _counter_values() -> Dict[str, float]:
    """The program's counters as its `/metrics` page exposes them
    (`common/metrics.py` text format), summed over labels by family."""
    from determined_tpu.common.metrics import REGISTRY, parse_exposition

    out = {key: 0.0 for key in COUNTERS.values()}
    for (name, _labels), value in parse_exposition(REGISTRY.render()).items():
        if name in COUNTERS:
            out[COUNTERS[name]] += value
    return out


def run(h) -> Dict[str, Any]:
    from benchmark import reference

    engine, params = build_engine(h)
    try:
        records = measure(h, engine)
    finally:
        with h.span("engine.stop"):
            engine.stop()
        # the pool's room goes to the reference's forward pass
        engine.cache_k = engine.cache_v = None
    # -- correct: served greedy tokens against the float32 reference -----
    done = [s for s in records.pop("served")
            if s.outcome in ("ok", "cut") and len(s.tokens) > 1]
    rng = np.random.default_rng([h.seed & 0xFFFFFFFF, 99])
    picks = [done[i] for i in sorted(rng.choice(
        len(done), replace=False,
        size=min(int(h.traffic.get("checked_requests", 4)), len(done)),
    ))] if done else []
    with h.span("reference"):
        check = reference.check_greedy(
            params,
            [{"prompt": s.prompt, "tokens": s.tokens} for s in picks],
            pad_to=engine.max_total,
        ) if picks else {"ok": False, "misses": ["no completed request"]}
    check["checked_requests"] = [s.index for s in picks]
    records["correct"] = check
    return records


def measure(h, engine) -> Dict[str, Any]:
    """One window of the mix against a started engine (left running:
    `benchmark/tools/sweep_rate.py` measures several rates on one)."""
    from benchmark import loadgen
    from determined_tpu.serving.engine import PromptTooLong, Shed

    t = h.traffic
    vocab = int(h.config["vocab_size"])
    closed = t["loop"] == "closed"
    preroll = float(t.get("preroll_seconds", 3.0))
    served: List[Served] = []
    lock = threading.Lock()
    readers: List[threading.Thread] = []
    late: List[float] = []
    stop = threading.Event()

    def read_stream(s: Served) -> None:
        for kind, payload in s.request.stream():
            now = time.perf_counter()
            if kind == "token":
                s.token_times.append(now)
                s.tokens.append(int(payload))
            elif kind == "done":
                s.outcome = "ok" if payload.get("reason") in (
                    "length", "eos") else str(payload.get("reason"))
            elif s.outcome == "pending":    # the engine's error event
                s.outcome = "cut" if stop.is_set() else "error"

    def send(s: Served) -> bool:
        s.submitted = time.perf_counter()
        try:
            with h.span("client.submit"):
                s.request = engine.submit(
                    s.prompt, max_new_tokens=s.max_new,
                    temperature=float(t.get("temperature", 0.0)))
        except (Shed, PromptTooLong) as e:
            s.outcome = "shed" if isinstance(e, Shed) else "error"
            return False
        return True

    # -- open loop -------------------------------------------------------
    def dispatch(schedule: List[Served]) -> None:
        for s in schedule:
            wait = s.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if stop.is_set():
                return
            late.append(time.perf_counter() - s.due)
            if send(s):
                th = threading.Thread(target=read_stream, args=(s,),
                                      daemon=True)
                th.start()
                readers.append(th)

    # -- closed loop -----------------------------------------------------
    indices = itertools.count()

    def client(mix: "loadgen.Mix") -> None:
        while not stop.is_set():
            i = next(indices)       # atomic under the interpreter lock
            r = mix.request(i)
            s = Served(i, time.perf_counter(), r.prompt, r.max_new_tokens,
                       True)
            with lock:
                served.append(s)
            if send(s):
                read_stream(s)
            else:
                time.sleep(0.05)    # shed: back off as Retry-After asks

    start = time.perf_counter() + 0.2
    lead = 2.0 if h.trace else 0.0   # the profiler takes a while to start
    t_open = start + max(preroll, lead + 0.5)
    if closed:
        mix = loadgen.Mix(t, h.seed, vocab)
        threads = [threading.Thread(target=client, args=(mix,), daemon=True)
                   for _ in range(int(t["clients"]))]
    else:
        warm = loadgen.Mix(t, h.seed, vocab, stream=1).open_loop(preroll)
        window = loadgen.Mix(t, h.seed, vocab).open_loop(h.seconds)
        served = [Served(-1 - r.index, start + r.due_s, r.prompt,
                         r.max_new_tokens, False) for r in warm]
        served += [Served(r.index, t_open + r.due_s, r.prompt,
                          r.max_new_tokens, True) for r in window]
        threads = [threading.Thread(target=dispatch, args=(list(served),),
                                    daemon=True)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_open - lead - time.perf_counter()))
    h.trace_start()
    time.sleep(max(0.0, t_open - time.perf_counter()))
    before = _counter_values()
    t0 = h.window_begin()
    time.sleep(max(0.0, t0 + h.seconds - time.perf_counter()))
    after = _counter_values()
    t1 = h.window_end()
    queued_at_close = engine.stats()["queued"]
    if closed:
        stop.set()
        # the callers' streams end when the engine stops; in a sweep
        # (engine kept) they end with their current request
    else:
        # Every request due in the window gets the chance to show its
        # first token; the run does not wait for streams to end (a long
        # answer outlasts the window), it cuts them when it stops.
        threads[0].join(timeout=5.0)
        deadline = time.perf_counter() + float(t.get("drain_seconds", 10.0))
        while time.perf_counter() < deadline and any(
                s.in_window and not s.token_times and s.outcome == "pending"
                for s in served):
            time.sleep(0.05)
        stop.set()
    t_stopped_waiting = time.perf_counter()

    # -- records ---------------------------------------------------------
    with lock:
        everything = list(served)
    if closed:
        # attempted: requests that ended (well or badly) inside the window
        def ended(s: Served) -> float:
            return s.token_times[-1] if s.token_times else s.submitted

        mine = [s for s in everything
                if s.outcome not in ("pending", "cut") and t0 <= ended(s) < t1]
    else:
        mine = [s for s in everything if s.in_window]
    # A stream still running when the run stops waiting is cut by the
    # run, not failed by the system; one that never answered failed.
    for s in mine:
        if s.outcome == "pending" and s.token_times:
            s.outcome = "cut"
    failed = [s for s in mine if s.outcome not in ("ok", "cut")]
    tokens_in_window = sum(
        1 for s in everything for x in s.token_times if t0 <= x < t1)

    def stamps(s: Served) -> Dict[str, Any]:
        r = s.request
        return {
            "due": s.due, "submitted": s.submitted, "outcome": s.outcome,
            "prompt_tokens": len(s.prompt), "token_times": s.token_times,
            "queue_wait_s": (r.t_admit - r.t_submit)
            if r is not None and r.t_admit else None,
        }

    return {
        "kind": "serve",
        "attempted": len(mine), "failed": len(failed),
        "t0": t0, "t1": t1, "t_stopped_waiting": t_stopped_waiting,
        "requests": [stamps(s) for s in mine],
        "served": mine,
        "tokens_in_window": tokens_in_window,
        "counters": {k: after[k] - before[k] for k in after},
        "serving": dict(t["serving"]),
        "notes": {
            "generator_late_ms_p50": 1e3 * loadgen.quantile(late, 0.5)
            if late else None,
            "generator_late_ms_max": 1e3 * max(late) if late else None,
            "outcomes": _count([s.outcome for s in mine]),
            "queued_at_close": queued_at_close,
            "engine_stats": {k: v for k, v in engine.stats().items()
                             if k in ("done", "shed", "tokens_emitted",
                                      "decode_kernel", "decode_backend",
                                      "device_peak_bytes", "pages_free")},
        },
    }


def _count(values: List[str]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for v in values:
        out[v] = out.get(v, 0) + 1
    return out
