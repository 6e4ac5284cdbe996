"""The traffic generator: the seed decides everything, the mix fixes the
amount of work, and latency is taken from the due time."""
import numpy as np

from benchmark import loadgen
from benchmark.end_to_end import ttft_p95_ms

CHAT = {
    "loop": "open",
    "arrivals": {"rate_per_s": 40.0, "cv": 1.0},
    "prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                      "min": 16, "max": 512},
    "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                      "min": 16, "max": 384},
    "max_total_tokens": 1024,
}


def schedule(seed, traffic=CHAT, seconds=20.0):
    return loadgen.Mix(traffic, seed, 50257).open_loop(seconds)


def test_same_seed_same_schedule():
    a, b = schedule(3), schedule(3)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    c = schedule(4)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_the_mix_fixes_the_work_not_the_draw():
    runs = [schedule(seed) for seed in range(1, 7)]
    assert {len(r) for r in runs} == {800}          # rate x seconds, always
    prompt = [sum(len(q.prompt) for q in r) for r in runs]
    output = [sum(q.max_new_tokens for q in r) for r in runs]
    assert max(prompt) / min(prompt) < 1.03
    assert max(output) / min(output) < 1.03


def test_arrivals_are_ascending_inside_the_window_and_bursty_on_demand():
    due = np.array([r.due_s for r in schedule(5)])
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 20.0
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2     # cv 1: Poisson
    burst = dict(CHAT, arrivals={"rate_per_s": 40.0, "cv": 3.0})
    gaps = np.diff([r.due_s for r in schedule(5, burst)])
    assert gaps.std() / gaps.mean() > 2.0


def test_lengths_respect_their_clips_and_the_context():
    for r in schedule(6):
        assert 16 <= len(r.prompt) <= 512
        assert 1 <= r.max_new_tokens <= 384
        assert len(r.prompt) + r.max_new_tokens <= 1024
        assert 0 <= min(r.prompt) and max(r.prompt) < 50257
    lens = sorted(len(r.prompt) for r in schedule(6))
    assert 85 <= lens[len(lens) // 2] <= 107        # the median asked for


def test_request_i_is_the_same_whoever_asks():
    mix, again = loadgen.Mix(CHAT, 9, 50257), loadgen.Mix(CHAT, 9, 50257)
    assert mix.request(300).prompt == again.request(300).prompt
    assert mix.request(300).prompt != mix.request(301).prompt
    other_stream = loadgen.Mix(CHAT, 9, 50257, stream=1)
    assert mix.request(300).prompt != other_stream.request(300).prompt


def test_shared_prefixes_and_repeats_are_data_not_code():
    shared = dict(CHAT, shared_prefix={
        "prompts": 16, "zipf_skew": 1.1,
        "tokens": {"dist": "uniform", "min": 256, "max": 512}},
        prompt_tokens={"dist": "uniform", "min": 300, "max": 600})
    reqs = schedule(2, shared, seconds=5.0)
    heads = {}
    for r in reqs:
        heads.setdefault(tuple(r.prompt[:128]), []).append(r)
    assert 2 <= len(heads) <= 16                    # a few hot prefixes
    hot = max(heads.values(), key=len)
    assert len(hot) > len(reqs) / 8                 # Zipf: one is popular
    assert hot[0].prompt[-1:] != hot[1].prompt[-1:] \
        or hot[0].prompt != hot[1].prompt           # suffixes differ
    rep = dict(CHAT, repeat={"motif_tokens": 8})
    p = schedule(2, rep, seconds=1.0)[0].prompt
    assert p[:8] == p[8:16]


def test_latency_is_taken_from_the_due_time():
    # a request due at 10.0, submitted late at 10.4, first token at 10.5:
    # the user waited 0.5 s, not 0.1 s; a request that never got a token
    # waited until the run stopped waiting
    records = {
        "kind": "serve", "t_stopped_waiting": 30.0,
        "requests": [
            {"due": 10.0, "submitted": 10.4, "token_times": [10.5, 10.6]},
            {"due": 11.0, "submitted": 11.0, "token_times": []},
        ],
    }
    assert ttft_p95_ms.ttfts_s(records) == [0.5, 19.0]


def test_quantile_is_linear_interpolation():
    assert loadgen.quantile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5
    assert loadgen.quantile([5.0], 0.95) == 5.0
