"""chip_smoke.py — the quickest proof that the platform still starts on a TPU.

Drives the main path once at GPT-2 small's full width (12 layers, d 768,
12 heads x 64, vocab 50304, seq 1024; random weights from a seed) through
the entry points a user would call, and checks what comes out:

  python chip_smoke.py            one chip: training phase, serving phase
  python chip_smoke.py --chips 4  four chips: the sharded path and the
                                  one-device run it is compared with, only

Training phase: `python -m determined_tpu.master.main` and `python -m
determined_tpu.agent.agent` (slots detected, not given), an experiment of
`SyntheticTrial` created through the API, a checkpoint to shared_fs, and
a second experiment that continues from it. Serving phase: `python -m
determined_tpu.serving.service` answering streaming requests. A last
child, once the chip is free again, rebuilds the same weights and batches
and compares both phases with a float32 `jax.numpy` reference.

A chip belongs to one process at a time, so THIS process never imports
jax: every phase runs in child processes, one after the other, and the
device description comes from a child. Each phase prints one JSON line
(times in them are observations, not metrics); the last line of stdout is
`{"ok": ..., "device": {"platform", "kind", "count"}}` and nothing else.
Anything but a TPU, any failed assertion, any phase failure: `"ok":
false` and exit code 1. The script never completes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from determined_tpu.common import compile_cache  # noqa: E402 — jax-free
from determined_tpu.common.metrics import (  # noqa: E402 — jax-free
    parse_exposition,
    sample_value,
)

#: Every process the smoke starts inherits this variable; `Run.stop_all`
#: finds whatever is left by it (tasks the agent spawned live in process
#: groups of their own, out of reach of a plain kill of the agent).
MARK = "DTPU_SMOKE_RUN"

# -- what is run (full width; depth and weights as the registry builds them) --
TRAIN_HPARAMS = {
    "model": "gpt2-small", "seq_len": 1024, "vocab_size": 50304,
    # 10.5 GiB peak of the chip's 16 by the compiler's memory_analysis()
    # (24 is 14.8: no headroom; compiled for a described v5e, PR 21).
    "batch_size": 16,
}
TRAIN_STEPS = 6        # scheduling units of one batch; then validate + save
CONTINUE_STEPS = 3     # the restored trial trains this many more
COMPARE_STEPS = 3      # losses held against the float32 reference
REFERENCE_MICROBATCH = 4   # rows per gradient-accumulation slice there
#: |bf16 Pallas loss - float32 dense loss| allowed per compared step. The
#: loss is ~11 (ln 50304 plus the z-loss); bf16 carries 8 bits of
#: mantissa and the errors of 16k tokens average out: the first chip run
#: (PR 21) saw 5e-5, 1e-6 and 1.5e-3 at steps 1-3. Ten times the worst.
LOSS_TOLERANCE = 0.02
SERVING_CONFIG = {
    "model": "small", "page_size": 128, "num_pages": 129,
    "max_pages_per_request": 8, "max_batch_size": 8, "prefill_rows": 4,
    "prefill_seq": 512, "max_new_tokens": 64, "prefix_cache": "on",
    "speculation": {"mode": "ngram", "draft_len": 4, "min_match": 2},
}
#: A served greedy token may differ from the float32 reference's argmax
#: only where the reference itself scores the two within this logit gap:
#: with random weights the top logits of 50304 sit ~0.1 apart, and bf16
#: activations move a logit by about a hundredth.
LOGIT_MARGIN = 0.05
SHARDED_MESHES = [{"fsdp": 2, "tensor": 2}, {"data": 2, "fsdp": 2}]
SHARDED_STEPS = 3


class SmokeFailure(Exception):
    """A phase failed or an assertion about its output did not hold."""


def check(cond: Any, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Parent-side plumbing: processes, HTTP, waiting
# ---------------------------------------------------------------------------
class Run:
    """One smoke run: its scratch directory (inside the checkout,
    git-ignored, removed at the end), its children and their logs."""

    def __init__(self) -> None:
        self.dir = os.path.join(compile_cache.cache_root(), "smoke")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.mark = f"{os.getpid()}-{int(time.time())}"
        self.env = dict(os.environ)
        self.env[MARK] = self.mark
        self.env["PYTHONUNBUFFERED"] = "1"
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH", "")) if p
        )
        # Write every compiled program to the cache, however quick its
        # compile: with jax's one-second floor a program near it is kept
        # by one run and not the next, and "a second run adds no entry"
        # could not be counted.
        self.env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        self.cache_dir = compile_cache.cache_dir()
        self.procs: List[subprocess.Popen] = []

    def log_path(self, name: str) -> str:
        return os.path.join(self.dir, f"{name}.log")

    def start(self, name: str, cmd: List[str]) -> subprocess.Popen:
        with open(self.log_path(name), "ab") as log:
            proc = subprocess.Popen(
                cmd, env=self.env, cwd=REPO, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        self.procs.append(proc)
        return proc

    def child(self, name: str, spec: Dict[str, Any],
              timeout: float) -> Dict[str, Any]:
        """Run `chip_smoke.py --child name spec` to its end; its last
        stdout line is its JSON result, its stderr goes to the log."""
        with open(self.log_path(name), "ab") as log:
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--child",
                     name, json.dumps(spec)],
                    env=self.env, cwd=REPO, stdout=subprocess.PIPE,
                    stderr=log, timeout=timeout, start_new_session=True,
                )
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"child {name} exceeded {timeout:.0f} s")
        lines = proc.stdout.decode().strip().splitlines()
        if proc.returncode != 0 or not lines:
            raise SmokeFailure(
                f"child {name} exited {proc.returncode}:\n"
                + self.tail(name)
            )
        return json.loads(lines[-1])

    def stop(self, proc: subprocess.Popen, sig: int = signal.SIGTERM,
             grace: float = 30.0) -> Optional[int]:
        if proc.poll() is None:
            proc.send_signal(sig)
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        return proc.returncode

    def stop_all(self) -> None:
        for proc in self.procs:
            self.stop(proc, grace=5.0)
        me = os.getpid()
        needle = f"{MARK}={self.mark}".encode()
        for pid in (int(p) for p in os.listdir("/proc") if p.isdigit()):
            if pid == me:
                continue
            try:
                with open(f"/proc/{pid}/environ", "rb") as f:
                    if needle in f.read().split(b"\0"):
                        os.kill(pid, signal.SIGKILL)
            except OSError:
                continue

    def tail(self, name: str, n: int = 40) -> str:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "(no log)"

    def all_tails(self) -> str:
        out = []
        for root, _dirs, files in os.walk(self.dir):
            for name in sorted(files):
                if name.endswith(".log"):
                    path = os.path.join(root, name)
                    with open(path, errors="replace") as f:
                        lines = f.readlines()[-25:]
                    out.append(f"--- {path}\n" + "".join(lines))
        return "\n".join(out)


def http_json(method: str, url: str, body: Any = None,
              timeout: float = 30.0) -> Any:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read() or b"null")


def wait_for(what: str, probe: Callable[[], Any], timeout: float,
             alive: Optional[subprocess.Popen] = None) -> Any:
    """Poll `probe` until it returns something truthy. A probe that
    cannot connect yet counts as "not yet"; `alive` exiting ends the wait."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if alive is not None and alive.poll() is not None:
            raise SmokeFailure(
                f"waiting for {what}: process exited {alive.returncode}"
            )
        try:
            got = probe()
        except (urllib.error.URLError, ConnectionError, socket.timeout):
            got = None
        if got:
            return got
        time.sleep(0.5)
    raise SmokeFailure(f"timed out after {timeout:.0f} s waiting for {what}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Training phase: master -> agent -> exec chain -> harness -> Trainer.fit
# ---------------------------------------------------------------------------
def run_experiment(url: str, config: Optional[Dict[str, Any]], *,
                   continue_from: Optional[int] = None,
                   max_length: Optional[int] = None,
                   timeout: float = 600.0) -> Dict[str, Any]:
    """Create (or continue) an experiment, wait for it, return what the
    API says about its one trial."""
    t0 = time.time()
    if continue_from is None:
        exp_id = http_json(
            "POST", f"{url}/api/v1/experiments", {"config": config}
        )["id"]
    else:
        exp_id = http_json(
            "POST", f"{url}/api/v1/experiments/{continue_from}/continue",
            {"max_length": max_length},
        )["id"]

    def done() -> Optional[str]:
        state = http_json("GET", f"{url}/api/v1/experiments/{exp_id}")["state"]
        return state if state in ("COMPLETED", "ERRORED", "CANCELED") else None

    state = wait_for(f"experiment {exp_id}", done, timeout)
    seconds = time.time() - t0
    trials = http_json(
        "GET", f"{url}/api/v1/experiments/{exp_id}/trials"
    )["trials"]
    check(len(trials) == 1, f"experiment {exp_id}: {len(trials)} trials")
    trial = trials[0]
    tid = trial["id"]

    def rows(group: str) -> List[Dict[str, Any]]:
        return http_json(
            "GET", f"{url}/api/v1/trials/{tid}/metrics?group={group}"
        )["metrics"]

    return {
        "experiment_id": exp_id, "state": state, "trial": trial,
        "training": rows("training"), "validation": rows("validation"),
        "profiling": rows("profiling"),
        "checkpoints": http_json(
            "GET", f"{url}/api/v1/trials/{tid}/checkpoints"
        )["checkpoints"],
        "create_to_done_seconds": round(seconds, 1),
    }


def launch_timeline(url: str, trial_id: int) -> Optional[Dict[str, float]]:
    """Seconds of the trial's submit -> schedule -> launch -> first_step
    critical path, as the master's trace store assembled it from the
    spans master, agent and trial shipped. `first_step` is the first call
    of the jitted step, which compiles synchronously
    (trainer/_trainer.py). None if the trace was not kept."""
    try:
        found = http_json("GET", f"{url}/api/v1/traces?root=allocation")
        for summary in found.get("traces", []):
            trace = http_json(
                "GET", f"{url}/api/v1/traces/{summary['trace_id']}"
            )
            roots = trace.get("tree") or [{}]
            task = roots[0].get("attributes", {}).get("task.id")
            if task == f"trial-{trial_id}":
                return {
                    seg["segment"]: round(float(seg["seconds"]), 2)
                    for seg in trace.get("critical_path") or []
                }
    except (urllib.error.URLError, KeyError, TypeError, ValueError):
        pass
    return None


def step_of(row: Dict[str, Any]) -> int:
    return int(row["steps_completed"])


def training_phase(run: Run, hparams: Dict[str, Any], steps: int,
                   more_steps: int) -> Dict[str, Any]:
    entries_before = compile_cache.entry_count(run.cache_dir)
    url = f"http://127.0.0.1:{free_port()}"
    master = run.start("master", [
        sys.executable, "-m", "determined_tpu.master.main",
        "--host", "127.0.0.1", "--port", url.rsplit(":", 1)[1],
        "--db", os.path.join(run.dir, "master.db"),
    ])
    wait_for("the master's API",
             lambda: http_json("GET", f"{url}/api/v1/master"), 60, master)
    # README's agent line: no --slots, so the chips are detected.
    agent = run.start("agent", [
        sys.executable, "-m", "determined_tpu.agent.agent",
        "--master-url", url, "--agent-id", "smoke-agent",
        "--state-dir", os.path.join(run.dir, "agent"),
    ])
    registered = wait_for(
        "the agent to register",
        lambda: http_json("GET", f"{url}/api/v1/agents")["agents"]
        .get("smoke-agent"),
        300, agent,
    )
    config = {
        "entrypoint": "determined_tpu.exec.builtin_trials:SyntheticTrial",
        "hyperparameters": hparams,
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": steps},
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 1,
        "min_validation_period": {"batches": steps},
        "min_checkpoint_period": {"batches": steps},
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(run.dir, "checkpoints"),
        },
        "profiling": {"enabled": True},   # device memory samples
        "max_restarts": 0,
    }
    first = run_experiment(url, config)
    check(first["state"] == "COMPLETED",
          f"trial ended {first['state']}:\n{run.all_tails()}")
    second = run_experiment(url, None, continue_from=first["experiment_id"],
                            max_length=steps + more_steps)
    check(second["state"] == "COMPLETED",
          f"restored trial ended {second['state']}:\n{run.all_tails()}")
    info = http_json("GET", f"{url}/api/v1/master")
    timelines = []
    for exp in (first, second):
        try:   # the allocation's span arrives a moment after COMPLETED
            timelines.append(wait_for(
                "the launch trace",
                lambda: launch_timeline(url, exp["trial"]["id"]), 15,
            ))
        except SmokeFailure:
            timelines.append(None)
    run.stop(agent)
    run.stop(master)

    # -- what the platform reported, held to what was asked -----------------
    losses = [float(r["body"]["loss"]) for r in first["training"]]
    check([step_of(r) for r in first["training"]] == list(range(1, steps + 1)),
          f"expected a report for each of {steps} steps, got "
          f"{[step_of(r) for r in first['training']]}")
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"non-finite loss among {losses}")
    check(first["validation"], "no validation was reported")
    saved = [c for c in first["checkpoints"] if c["state"] == "COMPLETED"]
    check(saved, "no checkpoint was registered")
    saved_step = max(int(c["steps_completed"]) for c in saved)
    check(saved_step == steps, f"checkpoint at step {saved_step}, not {steps}")
    resumed = [step_of(r) for r in second["training"]]
    check(resumed == list(range(steps + 1, steps + more_steps + 1)),
          f"restored trial should report steps {steps + 1}.."
          f"{steps + more_steps} (continuing the checkpoint), got {resumed}")
    devices = registered.get("devices") or []
    check(registered["slots"] == 1 and len(devices) == 1,
          f"agent registered {registered['slots']} slots / {devices}")
    peaks = [
        v for r in first["profiling"] for k, v in r["body"].items()
        if k.endswith("_peak_bytes_in_use")
    ]
    return {
        "phase": "training",
        "device": {"platform": devices[0]["platform"],
                   "kind": devices[0]["kind"], "count": len(devices)},
        "model": hparams, "steps": steps, "losses": losses,
        "validation_loss": float(
            first["validation"][-1]["body"]["loss"]
        ),
        "checkpoint_step": saved_step,
        "restored_steps": resumed,
        "restored_losses": [
            float(r["body"]["loss"]) for r in second["training"]
        ],
        "trial_seed": int(first["trial"].get("seed") or 0),
        "launch_timeline_seconds": timelines,
        "create_to_done_seconds": [first["create_to_done_seconds"],
                                   second["create_to_done_seconds"]],
        "peak_device_bytes": int(max(peaks)) if peaks else None,
        "scheduler_fit": info.get("scheduler_fit"),
        "compile_cache": {
            "dir": run.cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.entry_count(run.cache_dir),
        },
    }


# ---------------------------------------------------------------------------
# Serving phase: python -m determined_tpu.serving.service -> GenerationEngine
# ---------------------------------------------------------------------------
def serving_requests(seed: int = 0) -> List[Dict[str, Any]]:
    """A few greedy requests of different shapes, tokens drawn from a
    seed: short; long enough to span pages and to cross a page edge while
    decoding; two that share a two-page prefix; one whose repeating
    pattern makes the n-gram proposer draft."""
    rng = random.Random(seed)

    def toks(n: int) -> List[int]:
        return [rng.randrange(50257) for _ in range(n)]

    shared = toks(256)
    motif = toks(8)
    return [
        {"name": "short", "prompt": toks(12), "max_new_tokens": 16},
        {"name": "spans_pages", "prompt": toks(250), "max_new_tokens": 16},
        {"name": "prefix_a", "prompt": shared + toks(20),
         "max_new_tokens": 8},
        {"name": "prefix_b", "prompt": shared + toks(20),
         "max_new_tokens": 8},
        {"name": "ngram", "prompt": motif * 6, "max_new_tokens": 24},
    ]


def stream_generate(url: str, request: Dict[str, Any]) -> Dict[str, Any]:
    """POST one streaming request; collect its SSE events."""
    body = {"prompt": request["prompt"], "stream": True, "temperature": 0,
            "max_new_tokens": request["max_new_tokens"]}
    req = urllib.request.Request(
        f"{url}/api/v1/generate", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    tokens: List[int] = []
    final: Dict[str, Any] = {"reason": "error", "error": "stream ended"}
    event = ""
    with urllib.request.urlopen(req, timeout=300) as resp:
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if event == "token":
                    tokens.append(int(data["token"]))
                elif event == "done":
                    final = data
                elif event == "error":
                    final = {"reason": "error", "error": data.get("error")}
    return {"name": request["name"], "prompt": request["prompt"],
            "tokens": tokens, "reason": final.get("reason"),
            "error": final.get("error")}


def serving_phase(run: Run, serving_config: Dict[str, Any],
                  requests: List[Dict[str, Any]]) -> Dict[str, Any]:
    entries_before = compile_cache.entry_count(run.cache_dir)
    url = f"http://127.0.0.1:{free_port()}"
    t0 = time.time()
    server = run.start("serving", [
        sys.executable, "-m", "determined_tpu.serving.service",
        "--host", "127.0.0.1", "--port", url.rsplit(":", 1)[1],
        "--config", json.dumps(serving_config),
    ])
    # The port opens only after the engine compiled prefill, decode and
    # verify: a kernel the compiler refuses ends the process here.
    try:
        wait_for("the generation service",
                 lambda: http_json("GET", f"{url}/healthz"), 900, server)
    except SmokeFailure as e:
        raise SmokeFailure(f"{e}\n{run.tail('serving')}")
    startup = time.time() - t0

    # "short" and "spans_pages" share the batch; the rest go one at a
    # time so that prefix_b finds prefix_a's pages cached.
    results: Dict[str, Dict[str, Any]] = {}

    def go(request: Dict[str, Any]) -> None:
        try:
            results[request["name"]] = stream_generate(url, request)
        except (OSError, ValueError) as e:
            results[request["name"]] = {
                "name": request["name"], "prompt": request["prompt"],
                "tokens": [], "reason": "error", "error": repr(e),
            }

    together = [threading.Thread(target=go, args=(r,)) for r in requests[:2]]
    for t in together:
        t.start()
    for t in together:
        t.join(timeout=600)
    for request in requests[2:]:
        go(request)
    stats = http_json("GET", f"{url}/api/v1/stats")
    with urllib.request.urlopen(f"{url}/metrics", timeout=30) as resp:
        failures = sample_value(
            parse_exposition(resp.read().decode()),
            "dtpu_serving_decode_failures_total",
        ) or 0.0
    code = run.stop(server, sig=signal.SIGINT, grace=60.0)

    done = [results[r["name"]] for r in requests]
    for request, got in zip(requests, done):
        check(got["reason"] in ("length", "eos"),
              f"request {got['name']} finished {got['reason']}: "
              f"{got['error']}\n{run.tail('serving')}")
        check(len(got["tokens"]) == request["max_new_tokens"]
              or got["reason"] == "eos",
              f"request {got['name']}: {len(got['tokens'])} tokens of "
              f"{request['max_new_tokens']}")
    check(code == 0, f"the service exited {code} on SIGINT")
    check(failures == 0, f"decode_failures_total = {failures}")
    spec = stats["speculation"]
    cache = stats.get("prefix_cache", {})
    return {
        "phase": "serving", "config": serving_config,
        "requests": [
            {"name": d["name"], "prompt_tokens": len(d["prompt"]),
             "generated": len(d["tokens"]), "reason": d["reason"]}
            for d in done
        ],
        "results": done,   # dropped before printing; the reference reads it
        "decode_kernel": stats["decode_kernel"],
        "decode_backend": stats["decode_backend"],
        "decode_failures": failures,
        "spec_proposed": spec["proposed_tokens"],
        "spec_accepted": spec["accepted_tokens"],
        "prefix_cache_hit_rate": stats.get("cache_hit_rate"),
        "prefix_cache": cache,
        "startup_seconds": round(startup, 1),
        "peak_device_bytes": stats.get("device_peak_bytes"),
        "compile_cache": {
            "dir": run.cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.entry_count(run.cache_dir),
        },
    }


def check_serving_paths(record: Dict[str, Any]) -> None:
    """The paths that must have run on the chip (asserted from what the
    server reported it ran, not from what its config selects)."""
    check(record["decode_kernel"] == "paged",
          f"decode_kernel is {record['decode_kernel']!r}, not 'paged'")
    check(record["decode_backend"] == "pallas",
          f"decode_backend is {record['decode_backend']!r}: the kernel "
          "did not run compiled on the chip")
    check(record["spec_proposed"] > 0,
          "the n-gram proposer never drafted: the verify step's "
          "multi-row path did not run")
    check((record["prefix_cache"] or {}).get("hits", 0) >= 1,
          f"no prefix-cache hit: {record['prefix_cache']}")


# ---------------------------------------------------------------------------
# Children (each owns the chip while it lives; results on the last line)
# ---------------------------------------------------------------------------
def device_record() -> Dict[str, Any]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_device_bytes() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def child_device(spec: Dict[str, Any]) -> Dict[str, Any]:
    from determined_tpu.data import native as native_loader

    return {
        "device": device_record(),
        "dataloader": (
            "native" if native_loader.load_library() is not None
            else "python"
        ),
    }


def float32_reference(hparams: Dict[str, Any]):
    """The same architecture in float32 with `impl="dense"` attention:
    plain einsum softmax, no Pallas, no bf16."""
    import dataclasses

    import jax.numpy as jnp

    from determined_tpu.models import get_model

    built = get_model(hparams["model"], **hparams.get("model_kw", {}))
    # layer_loop="scan": one compiled block whatever the depth — the
    # unrolled float32 program takes minutes to compile.
    return type(built)(dataclasses.replace(
        built.config, dtype=jnp.float32, attn_impl="dense",
        layer_loop="scan",
    ))


def compiled_step_text(trainer, raw_batch) -> str:
    """The compiled text of the Trainer's own train step (through the
    persistent cache when a process before this one compiled it). A
    Pallas kernel shows in it as a `tpu_custom_call`; the reference
    attention has none."""
    import numpy as np

    step = trainer._step_fn or trainer._build_step_fn()
    return step.lower(
        trainer.state, trainer._put_batch(raw_batch), np.float32(1.0),
        trainer._zero_skips(),
    ).compile().as_text()


def reference_training(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Same seed, same batches, same optimizer as the trial — float32
    dense attention through `GPT.apply`, the loss written out here."""
    import jax
    import jax.numpy as jnp

    from determined_tpu import core
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.trainer import Trainer

    hparams, seed = spec["hparams"], spec["trial_seed"]
    # 1. Evidence of the path: the step the trial ran, built again here by
    # the same Trainer from the same hyperparameters and compiled (through
    # the cache the trial's process wrote).
    trial = SyntheticTrial(hparams)
    batches = iter(trial.build_training_data())
    first = next(batches)
    t0 = time.time()
    text = compiled_step_text(
        Trainer(trial, core._context._dummy_init(), seed=seed), first
    )
    step_compile_seconds = time.time() - t0

    # 2. The float32 reference run.
    model = float32_reference(hparams)
    tx = trial.build_optimizer()
    step = reference_train_step(model, tx)
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.PRNGKey(seed))
        opt = tx.init(params)
        losses = []
        for tokens in [first] + [next(batches)
                                 for _ in range(spec["steps"] - 1)]:
            params, opt, loss = step(params, opt, jnp.asarray(tokens["tokens"]))
            losses.append(float(loss))
    return {
        "reference_losses": losses,
        "step_tpu_custom_calls": text.count("tpu_custom_call"),
        "step_compile_seconds": round(step_compile_seconds, 1),
    }


def reference_train_step(model, tx):
    """One optimizer step of the float32 reference, jitted: next-token
    cross-entropy plus the z-loss written out over `model.apply`'s
    logits, gradients averaged over micro-batches of
    REFERENCE_MICROBATCH rows (equal sizes, so the mean of their means is
    the batch's) — float32 dense attention at the trial's whole batch
    does not fit the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    z_loss = model.config.z_loss

    def loss_fn(params, tokens):
        logits = model.apply(params, tokens).astype(jnp.float32)[:, :-1]
        lse = jax.nn.logsumexp(logits, axis=-1)
        target = jnp.take_along_axis(
            logits, tokens[:, 1:, None], axis=-1
        )[..., 0]
        return jnp.mean(lse - target) + z_loss * jnp.mean(jnp.square(lse))

    def step(params, opt, tokens):
        micro = tokens.reshape(-1, REFERENCE_MICROBATCH, tokens.shape[1])

        def add_one(total, rows):
            return jax.tree.map(
                jnp.add, total, jax.value_and_grad(loss_fn)(params, rows)
            ), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        total, _ = jax.lax.scan(add_one, zero, micro)
        loss, grads = jax.tree.map(lambda x: x / micro.shape[0], total)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def reference_serving(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Teacher-forced check of every served token: one float32 forward
    over prompt + generated, position i must argmax-predict token i+1 —
    or score it within `margin` of the argmax (a bf16 near-tie)."""
    import jax
    import numpy as np

    model = float32_reference({"model": spec["model"]})
    apply = jax.jit(model.apply)
    with jax.default_matmul_precision("highest"):
        # the same seed-0 weights as serving/service.py build_engine
        params = model.init(jax.random.PRNGKey(0))
        width = spec["pad_to"]
        exact = near = 0
        worst_gap = 0.0
        misses: List[str] = []
        for got in spec["results"]:
            seq = got["prompt"] + got["tokens"]
            padded = np.zeros((1, width), np.int32)
            padded[0, :len(seq)] = seq
            logits = np.asarray(apply(params, padded)[0], np.float32)
            for i in range(len(got["prompt"]) - 1, len(seq) - 1):
                row, served = logits[i], seq[i + 1]
                gap = float(row.max() - row[served])
                if gap == 0.0:
                    exact += 1
                elif gap <= spec["margin"]:
                    near += 1
                    worst_gap = max(worst_gap, gap)
                else:
                    misses.append(
                        f"{got['name']}[{i + 1 - len(got['prompt'])}]: "
                        f"served {served}, reference "
                        f"{int(row.argmax())}, gap {gap:.4f}"
                    )
    return {"tokens_exact": exact, "tokens_near_tie": near,
            "worst_near_tie_gap": round(worst_gap, 5), "misses": misses}


def child_reference(spec: Dict[str, Any]) -> Dict[str, Any]:
    compile_cache.enable()
    out: Dict[str, Any] = {"device": device_record()}
    if "training" in spec:
        out["training"] = reference_training(spec["training"])
    if "serving" in spec:
        out["serving"] = reference_serving(spec["serving"])
    out["peak_device_bytes"] = peak_device_bytes()
    return out


def child_sharded(spec: Dict[str, Any]) -> Dict[str, Any]:
    """GPT-2 small through `Trainer` on meshes that use every chip on two
    axes, against the same global batch and seed on a one-device mesh in
    this same process."""
    import jax

    from determined_tpu import core
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.parallel.mesh import MeshConfig, make_mesh
    from determined_tpu.trainer import Batch, Trainer

    cache_dir = compile_cache.enable()
    entries_before = compile_cache.entry_count(cache_dir)
    devices = jax.devices()
    steps = spec["steps"]

    def run(mesh_cfg: Dict[str, int], devs) -> Dict[str, Any]:
        ctx = core._context._dummy_init()
        mesh = make_mesh(MeshConfig(**mesh_cfg), devices=devs)
        trainer = Trainer(SyntheticTrial(spec["hparams"]), ctx, mesh=mesh)
        t0 = time.time()
        trainer.fit(max_length=Batch(steps), report_period=Batch(1))
        seconds = time.time() - t0
        losses = [
            float(m["loss"]) for group, _step, m in ctx.train._reported
            if group == "training"
        ]
        state = trainer.state

        def per_device(tree) -> Dict[int, float]:
            total = sum(x.size for x in jax.tree.leaves(tree))
            held: Dict[int, int] = {}
            for x in jax.tree.leaves(tree):
                for shard in x.addressable_shards:
                    held[shard.device.id] = (
                        held.get(shard.device.id, 0) + shard.data.size
                    )
            return {d: round(v / total, 4) for d, v in sorted(held.items())}

        text = compiled_step_text(
            trainer, next(iter(trainer.trial.build_training_data()))
        )
        return {
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "losses": losses, "fit_seconds": round(seconds, 1),
            "params_share": per_device(state["params"]),
            "opt_state_share": per_device(state["opt_state"]),
            "collectives": {
                op: text.count(f" {op}(") + text.count(f" {op}-start(")
                for op in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")
            },
            "tpu_custom_calls": text.count("tpu_custom_call"),
        }

    single = run({}, devices[:1])
    sharded = [run(cfg, devices) for cfg in spec["meshes"]]
    return {
        "phase": "sharded", "device": device_record(),
        "model": spec["hparams"], "steps": steps,
        "single_device": single, "sharded": sharded,
        "peak_device_bytes": peak_device_bytes(),
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.entry_count(cache_dir),
        },
    }


CHILDREN = {"device": child_device, "reference": child_reference,
            "sharded": child_sharded}


# ---------------------------------------------------------------------------
# Verdicts on what the children measured
# ---------------------------------------------------------------------------
def check_training_reference(record: Dict[str, Any], ref: Dict[str, Any],
                             tolerance: float) -> None:
    record["reference_losses"] = ref["reference_losses"]
    record["loss_tolerance"] = tolerance
    record["step_tpu_custom_calls"] = ref["step_tpu_custom_calls"]
    record["step_compile_seconds_warm"] = ref["step_compile_seconds"]
    record["attention_path"] = (
        "pallas flash (tpu_custom_call in the compiled step)"
        if ref["step_tpu_custom_calls"] else "reference (no custom call)"
    )
    for i, (got, want) in enumerate(
        zip(record["losses"], ref["reference_losses"])
    ):
        check(abs(got - want) <= tolerance,
              f"step {i + 1}: loss {got:.4f} vs float32 reference "
              f"{want:.4f} (tolerance {tolerance})")
    check(ref["step_tpu_custom_calls"] > 0,
          "the compiled train step holds no tpu_custom_call: the Pallas "
          "flash kernels did not run")


def check_serving_reference(record: Dict[str, Any],
                            ref: Dict[str, Any], margin: float) -> None:
    record.update({
        "logit_margin": margin, "tokens_exact": ref["tokens_exact"],
        "tokens_near_tie": ref["tokens_near_tie"],
        "worst_near_tie_gap": ref["worst_near_tie_gap"],
    })
    check(not ref["misses"],
          "served tokens disagree with the float32 reference beyond the "
          f"logit margin {margin}: {ref['misses'][:5]}")
    check(ref["tokens_exact"] > 0, "no served token was compared")


def check_sharded(record: Dict[str, Any], tolerance: float) -> None:
    n = record["device"]["count"]
    want = record["single_device"]["losses"]
    for run in record["sharded"]:
        label = run["mesh"]
        for i, (got, ref) in enumerate(zip(run["losses"], want)):
            check(abs(got - ref) <= tolerance,
                  f"mesh {label} step {i + 1}: loss {got:.4f} vs one "
                  f"device {ref:.4f} (tolerance {tolerance})")
        check(len(run["losses"]) == record["steps"],
              f"mesh {label}: {len(run['losses'])} losses")
        # params shard over fsdp x tensor and replicate over data; a few
        # small vectors (biases, norms) stay whole: allow a tenth over.
        ways = run["mesh"].get("fsdp", 1) * run["mesh"].get("tensor", 1)
        for what in ("params_share", "opt_state_share"):
            share = run[what]
            check(len(share) == n,
                  f"mesh {label}: {what} on {len(share)} of {n} devices")
            check(all(1 / ways <= s <= 1.1 / ways + 0.01
                      for s in share.values()),
                  f"mesh {label}: {what} {share}, expected ~1/{ways} each")
        check(run["collectives"]["all-gather"] > 0
              and (run["collectives"]["all-reduce"] > 0
                   or run["collectives"]["reduce-scatter"] > 0),
              f"mesh {label}: expected all-gather and a gradient "
              f"reduction, found {run['collectives']}")
        check(run["tpu_custom_calls"] > 0,
              f"mesh {label}: no Pallas kernel in the compiled step")


# ---------------------------------------------------------------------------
def emit(record: Dict[str, Any]) -> None:
    print(json.dumps({k: v for k, v in record.items() if k != "results"}),
          flush=True)


def one_chip(run: Run, device: Dict[str, Any]) -> None:
    probe = run.child("device", {}, timeout=300)
    device.update(probe["device"])
    check(device["platform"] == "tpu",
          f"jax found no accelerator: devices are {device}")
    check(device["count"] == 1,
          f"{device['count']} chips: run the four-chip path with --chips 4")
    train = training_phase(run, TRAIN_HPARAMS, TRAIN_STEPS, CONTINUE_STEPS)
    train["dataloader"] = probe["dataloader"]
    serve = serving_phase(run, SERVING_CONFIG, serving_requests())
    ref = run.child("reference", {
        "training": {"hparams": TRAIN_HPARAMS, "steps": COMPARE_STEPS,
                     "trial_seed": train["trial_seed"]},
        "serving": {"model": "gpt2-small", "results": serve["results"],
                    "margin": LOGIT_MARGIN,
                    "pad_to": SERVING_CONFIG["prefill_seq"]},
    }, timeout=600)
    train["reference_peak_device_bytes"] = ref["peak_device_bytes"]
    try:
        check_training_reference(train, ref["training"], LOSS_TOLERANCE)
        check_serving_paths(serve)
        check_serving_reference(serve, ref["serving"], LOGIT_MARGIN)
        check({train["device"]["platform"], ref["device"]["platform"]}
              == {"tpu"}, "a phase ran on something other than the tpu")
        check(train["device"]["kind"] == device["kind"],
              f"agent registered {train['device']['kind']!r}, jax reports "
              f"{device['kind']!r}")
    finally:   # both phase lines print, pass or fail
        emit(train)
        emit(serve)


def four_chips(run: Run, device: Dict[str, Any]) -> None:
    record = run.child("sharded", {
        "hparams": TRAIN_HPARAMS, "steps": SHARDED_STEPS,
        "meshes": SHARDED_MESHES,
    }, timeout=1100)
    device.update(record["device"])
    try:
        check(device["platform"] == "tpu",
              f"jax found no accelerator: devices are {device}")
        check(device["count"] == 4, f"{device['count']} chips, not 4")
        check_sharded(record, LOSS_TOLERANCE)
    finally:
        emit(record)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--child", nargs=2, metavar=("NAME", "SPEC"),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        name, spec = args.child
        print(json.dumps(CHILDREN[name](json.loads(spec))), flush=True)
        return 0

    run = Run()
    device: Dict[str, Any] = {"platform": None, "kind": None, "count": 0}
    ok = False
    try:
        (four_chips if args.chips == 4 else one_chip)(run, device)
        ok = True
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
    except Exception:  # noqa: BLE001 — a crash here is a failed smoke too
        import traceback

        traceback.print_exc()
        print(run.all_tails(), file=sys.stderr, flush=True)
    finally:
        run.stop_all()
        # On failure keep the logs and drop only the gigabytes.
        shutil.rmtree(
            run.dir if ok else os.path.join(run.dir, "checkpoints"),
            ignore_errors=True,
        )
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
