"""Control-plane IPC tests (ref strategy: harness/tests/test_ipc.py)."""
import pytest

from tests.parallel import run_parallel


@pytest.mark.parametrize("size", [1, 2, 4])
def test_allgather(size):
    out = run_parallel(size, lambda ctx: ctx.allgather(ctx.rank * 10))
    for res in out:
        assert res == [r * 10 for r in range(size)]


def test_gather_ordering():
    def fn(ctx):
        return ctx.gather(f"rank-{ctx.rank}")

    out = run_parallel(4, fn)
    assert out[0] == [f"rank-{r}" for r in range(4)]
    for r in range(1, 4):
        assert out[r] is None


def test_broadcast():
    def fn(ctx):
        return ctx.broadcast({"payload": 42} if ctx.is_chief else None)

    out = run_parallel(3, fn)
    assert all(res == {"payload": 42} for res in out)


def test_concurrent_channels():
    """Collectives on different channels may run from different threads
    concurrently without stealing each other's frames — the contract the
    async checkpoint writer relies on (its collective upload rides the
    'checkpoint' channel while the step loop broadcasts preemption flags
    on 'main')."""
    import threading

    def fn(ctx):
        results = {}

        def ckpt_thread():
            # Background "checkpoint": broadcast + gather + barrier on its
            # own channel, deliberately racing the main-channel traffic.
            for i in range(20):
                sid = ctx.broadcast(
                    f"ckpt-{i}" if ctx.is_chief else None, channel="checkpoint"
                )
                gathered = ctx.gather((ctx.rank, sid), channel="checkpoint")
                if ctx.is_chief:
                    assert [g[1] for g in gathered] == [sid] * ctx.size
                ctx.barrier(channel="checkpoint")
            results["ckpt"] = True

        t = threading.Thread(target=ckpt_thread)
        t.start()
        flags = [ctx.broadcast(i if ctx.is_chief else None) for i in range(50)]
        t.join(timeout=30)
        assert not t.is_alive(), "checkpoint-channel thread hung"
        return flags, results.get("ckpt")

    out = run_parallel(3, fn)
    for flags, ckpt_ok in out:
        assert flags == list(range(50))
        assert ckpt_ok is True


def test_close_wakes_blocked_recv():
    """A thread blocked in a timeout-less collective is failed loudly when
    the endpoint closes, instead of sleeping forever on a condition nothing
    will notify."""
    import threading

    from determined_tpu.common import ipc

    port = ipc.free_port()
    results = {}

    def chief():
        srv = ipc.ChiefServer(1, port=port)
        srv.accept()
        srv.close()

    def worker():
        cli = ipc.WorkerClient(f"127.0.0.1:{port}", 1)

        def blocked():
            try:
                cli.recv(channel="never")
            except BaseException as e:  # noqa: BLE001
                results["err"] = e

        t = threading.Thread(target=blocked)
        t.start()
        import time

        time.sleep(0.3)  # let it block
        cli.close()
        t.join(timeout=10)
        results["done"] = not t.is_alive()

    tc, tw = threading.Thread(target=chief), threading.Thread(target=worker)
    tc.start(); tw.start()
    tc.join(timeout=15); tw.join(timeout=15)
    assert results.get("done") is True
    assert isinstance(results.get("err"), RuntimeError)


def test_barrier_and_repeated_collectives():
    def fn(ctx):
        acc = []
        for i in range(5):
            acc.append(ctx.allgather((ctx.rank, i)))
            ctx.barrier()
        return acc

    out = run_parallel(3, fn)
    for res in out:
        for i, round_result in enumerate(res):
            assert round_result == [(r, i) for r in range(3)]


def test_send_is_not_starved_by_the_receiver_loop():
    """The receiver thread polls the socket under the same lock send()
    needs. Python locks are not fair: a loop that re-takes the lock the
    instant it drops it kept a waiting send() out for several poll
    intervals in a quiet process and for minutes in a busy trial (a
    2-process gang hung in its checkpoint gather). With the loop yielding
    between polls a send waits for at most the poll in flight."""
    import threading
    import time

    from determined_tpu.common import ipc

    chief = ipc.ChiefServer(1)
    box = {}
    t = threading.Thread(
        target=lambda: box.update(
            w=ipc.WorkerClient(f"127.0.0.1:{chief.port}", 1)
        )
    )
    t.start()
    chief.accept(timeout_s=30)
    t.join(timeout=30)
    worker = box["w"]
    try:
        spent = 0.0
        for i in range(40):
            t0 = time.monotonic()
            worker.send(i)
            spent += time.monotonic() - t0
            assert chief.gather(timeout_s=30) == [i]
        # <= one 50 ms poll each (2 s in all); the unfair loop took 3.7-5 s.
        assert spent < 3.0, f"40 sends waited {spent:.2f} s on the lock"
    finally:
        worker.close()
        chief.close()
