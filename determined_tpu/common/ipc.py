"""Chief/worker control-plane IPC over ZeroMQ.

TPU-native analog of the reference's ZMQ star (ref:
harness/determined/ipc.py:32,169 — ZMQBroadcastServer/ZMQBroadcastClient).
This carries *control-plane* python objects only (metrics dicts, checkpoint
selectors, preemption flags) — never tensors. The data plane is XLA
collectives over ICI/DCN, compiled into the jitted program.

Design differences from the reference:

- instead of PUB/SUB + PUSH/PULL (which needs a slow-joiner sync dance), a
  single ROUTER socket on the chief and DEALER sockets on workers. ROUTER
  gives reliable, addressable delivery, so gather/broadcast need no sync
  protocol;
- every message carries a **channel** tag, and each endpoint runs one
  receiver thread that sorts arrivals into per-(rank, channel) inboxes.
  Channels make concurrent collectives from different threads safe as long
  as each thread uses its own channel: the async checkpoint writer runs its
  collective upload on the "checkpoint" channel while the step loop polls
  preemption on "main", and neither can steal the other's frames. (ZMQ
  sockets are not thread-safe, so all socket ops are mutex-guarded and only
  the receiver thread ever recv()s after startup.)
"""
from __future__ import annotations

import logging
import pickle
import socket
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional

import zmq

logger = logging.getLogger("determined_tpu.ipc")

_HELLO = b"__hello__"
_POLL_MS = 50  # receiver-thread recv timeout; bounds send-lock hold time
CHANNEL_MAIN = "main"


def free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _Inbox:
    """Receiver-side state shared by both ends of the star: per-key FIFOs
    of arrived frames, a condition variable for waiters, and receiver-death
    propagation (a dead receiver must fail waiters loudly — they would
    otherwise block forever on a condition nothing will ever notify)."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._queues: Dict[Hashable, List[Any]] = {}
        self._error: Optional[BaseException] = None

    def put(self, key: Hashable, obj: Any) -> None:
        with self._cond:
            self._queues.setdefault(key, []).append(obj)
            self._cond.notify_all()

    def die(self, err: BaseException) -> None:
        with self._cond:
            self._error = err
            self._cond.notify_all()

    def get(self, key: Hashable, timeout_s: Optional[float], what: str) -> Any:
        """Pop the next frame for `key`, waiting as needed. Raises
        TimeoutError on deadline and RuntimeError if the receiver died."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        with self._cond:
            while not self._queues.get(key):
                if self._error is not None:
                    raise RuntimeError(
                        f"IPC receiver thread died: {self._error!r}"
                    ) from self._error
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(f"{what} timed out")
                self._cond.wait(timeout=remaining)
            return self._queues[key].pop(0)


class _ReceiverLoop:
    """One background thread owning all recv()s on a socket; `handle`
    stashes each payload. ZMQError during shutdown is an orderly exit; any
    other failure (ETERM, a malformed frame in `handle`) is routed to the
    inbox so blocked collectives fail instead of hanging."""

    def __init__(
        self,
        name: str,
        sock_lock: threading.Lock,
        recv: Callable[[], bytes],
        handle: Callable[[bytes], None],
        inbox: _Inbox,
        is_closed: Callable[[], bool],
    ) -> None:
        self._sock_lock = sock_lock
        self._recv = recv
        self._handle = handle
        self._inbox = inbox
        self._is_closed = is_closed
        self.thread = threading.Thread(target=self._run, name=name, daemon=True)

    def _run(self) -> None:
        while not self._is_closed():
            try:
                try:
                    with self._sock_lock:
                        if self._is_closed():
                            return
                        payload = self._recv()
                except zmq.Again:
                    # Let a sender blocked on the socket lock have it:
                    # Python locks are not fair, and this loop re-taking
                    # the lock the instant it drops it can starve a
                    # send() for minutes (seen as a 2-process gang hung
                    # in its checkpoint gather).
                    time.sleep(0.001)  # resilience-ok: lock hand-off yield, not a retry
                    continue
                except zmq.ZMQError as e:
                    if self._is_closed():
                        return  # orderly close() tearing the socket down
                    self._inbox.die(e)
                    return
                self._handle(payload)
            except BaseException as e:  # noqa: BLE001 — malformed frame etc.
                self._inbox.die(e)
                return


class ChiefServer:
    """Runs on rank 0. Accepts `size - 1` worker connections."""

    def __init__(self, num_workers: int, port: int = 0) -> None:
        self._num_workers = num_workers
        self._ctx = zmq.Context.instance()
        self._sock = self._ctx.socket(zmq.ROUTER)
        self._sock.setsockopt(zmq.ROUTER_MANDATORY, 1)
        if port == 0:
            self.port = self._sock.bind_to_random_port("tcp://*")
        else:
            self._sock.bind(f"tcp://*:{port}")
            self.port = port
        self._identities: List[bytes] = []
        # Arrived-but-unclaimed frames, keyed (rank, channel). ZMQ preserves
        # per-connection ordering, so per-key FIFOs keep collective rounds
        # aligned without sequence numbers.
        self._inbox = _Inbox()
        self._sock_lock = threading.Lock()
        self._closed = False
        self._receiver: Optional[_ReceiverLoop] = None

    def _stash(self, payload: bytes) -> None:
        if payload == _HELLO:
            return
        rank, channel, obj = pickle.loads(payload)
        self._inbox.put((rank, channel), obj)

    def accept(self, timeout_s: float = 120.0) -> None:
        """Wait for all workers to say hello, then start the receiver."""
        self._sock.setsockopt(zmq.RCVTIMEO, int(timeout_s * 1000))
        while len(self._identities) < self._num_workers:
            ident, payload = self._sock.recv_multipart()
            if payload == _HELLO:
                if ident not in self._identities:
                    self._identities.append(ident)
            else:
                self._stash(payload)
        self._sock.setsockopt(zmq.RCVTIMEO, _POLL_MS)
        self._receiver = _ReceiverLoop(
            "dtpu-ipc-chief-recv",
            self._sock_lock,
            lambda: self._sock.recv_multipart()[1],
            self._stash,
            self._inbox,
            lambda: self._closed,
        )
        self._receiver.thread.start()

    def gather(
        self, timeout_s: Optional[float] = None, channel: str = CHANNEL_MAIN
    ) -> List[Any]:
        """Receive one object from every worker (ranks 1..n), rank-ordered."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        out: List[Any] = []
        for rank in range(1, self._num_workers + 1):
            remaining = None if deadline is None else deadline - time.monotonic()
            out.append(
                self._inbox.get(
                    (rank, channel),
                    remaining,
                    f"gather({channel!r}) waiting for rank {rank}",
                )
            )
        return out

    def broadcast(self, obj: Any, channel: str = CHANNEL_MAIN) -> None:
        payload = pickle.dumps((channel, obj))
        with self._sock_lock:
            for ident in self._identities:
                try:
                    self._sock.send_multipart([ident, payload])
                except zmq.ZMQError as e:
                    # ROUTER_MANDATORY surfaces an unreachable peer
                    # (EHOSTUNREACH): under elastic resize a reclaimed
                    # worker is EXPECTED to be gone, and the chief's
                    # boundary broadcast must keep reaching the survivors
                    # — one dead rank must not take the control plane (and
                    # with it the whole gang) down.
                    logger.warning(
                        "broadcast to worker %r failed (%s); peer presumed "
                        "dead", ident, e,
                    )

    def close(self) -> None:
        self._closed = True
        if self._receiver is not None:
            self._receiver.thread.join(timeout=5)
        # Wake any thread still blocked in gather(): after close nothing
        # will ever notify its condition (pre-rewrite, the socket teardown
        # itself failed the blocked recv).
        self._inbox.die(RuntimeError("IPC endpoint closed"))
        # Bounded linger: lets in-flight frames flush from the IO thread
        # without pinning dead sockets forever. linger=0 here would race
        # with delivery of the last send.
        with self._sock_lock:
            self._sock.close(linger=10_000)


class WorkerClient:
    """Runs on ranks > 0; connects to the chief."""

    def __init__(self, chief_addr: str, rank: int) -> None:
        self._rank = rank
        self._ctx = zmq.Context.instance()
        self._sock = self._ctx.socket(zmq.DEALER)
        self._sock.connect(f"tcp://{chief_addr}")
        self._sock.setsockopt(zmq.RCVTIMEO, _POLL_MS)
        self._sock.send(_HELLO)
        self._inbox = _Inbox()
        self._sock_lock = threading.Lock()
        self._closed = False
        self._receiver = _ReceiverLoop(
            "dtpu-ipc-worker-recv",
            self._sock_lock,
            self._sock.recv,
            self._stash,
            self._inbox,
            lambda: self._closed,
        )
        self._receiver.thread.start()

    def _stash(self, payload: bytes) -> None:
        channel, obj = pickle.loads(payload)
        self._inbox.put(channel, obj)

    def send(self, obj: Any, channel: str = CHANNEL_MAIN) -> None:
        payload = pickle.dumps((self._rank, channel, obj))
        with self._sock_lock:
            self._sock.send(payload)

    def recv(
        self, timeout_s: Optional[float] = None, channel: str = CHANNEL_MAIN
    ) -> Any:
        # No default timeout: the chief may legitimately spend many minutes
        # between collectives (e.g. uploading a multi-GB shard before the
        # checkpoint barrier); a ticking timeout here would kill the job.
        return self._inbox.get(channel, timeout_s, f"recv({channel!r})")

    def close(self) -> None:
        self._closed = True
        self._receiver.thread.join(timeout=5)
        self._inbox.die(RuntimeError("IPC endpoint closed"))
        with self._sock_lock:
            self._sock.close(linger=10_000)
