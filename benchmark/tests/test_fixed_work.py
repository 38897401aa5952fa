"""A closed-loop cell as a fixed piece of work (PR 28): the mix sets the
lengths and the seed the content, the first requests are cut as in a job
caught mid-way, and the window closes on a token count. CPU only, no engine:
the generator, the reduction and the steps as counts."""
import itertools
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.generators import request_stream  # noqa: E402
from benchmark.reduce import client  # noqa: E402
from benchmark.tools import loop_steps  # noqa: E402

TRAFFIC = spec.HERE / "traffic"
ROLLOUT = spec.load_json(TRAFFIC / "rollout.json")
CLIENTS = ROLLOUT["loop"]["clients"]


def _take(mix, seed, n=64):
    return list(itertools.islice(request_stream.stream(mix, seed, 50257), n))


def _lengths(reqs):
    return [(len(r["prompt"]), r["max_tokens"]) for r in reqs]


# (a) the mix sets the lengths, the seed the content ----------------------

@pytest.mark.parametrize("seeds", [(1, 2), (7, 3000000011)])
def test_one_lengths_seed_gives_every_seed_the_same_lengths(seeds):
    a, b = (_take(ROLLOUT, s) for s in seeds)
    assert _lengths(a) == _lengths(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))


def test_another_lengths_seed_gives_other_lengths():
    other = spec.merged(ROLLOUT, {"lengths_seed": ROLLOUT["lengths_seed"] + 1})
    assert _lengths(_take(ROLLOUT, 1)) != _lengths(_take(other, 1))
    # and the token ids follow the seed alone: equal where lengths allow
    a, b = _take(ROLLOUT, 5, 1)[0], _take(other, 5, 1)[0]
    n = min(len(a["prompt"]), len(b["prompt"]))
    assert a["prompt"][:n] == b["prompt"][:n]


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_a_mix_without_the_key_draws_lengths_from_the_seed_as_before(name):
    mix = spec.load_json(TRAFFIC / f"{name}.json")
    assert "lengths_seed" not in mix and "start" not in mix
    assert _lengths(_take(mix, 1)) != _lengths(_take(mix, 2))
    assert _take(mix, 4) == _take(mix, 4)


def test_lengths_seed_fixes_shared_documents_too():
    mix = spec.merged(spec.load_json(TRAFFIC / "docqa.json"),
                      {"lengths_seed": 3})
    a, b = _take(mix, 1, 40), _take(mix, 2, 40)
    assert _lengths(a) == _lengths(b)
    assert [r["shared_tokens"] for r in a] == [r["shared_tokens"] for r in b]


# (b) the stationary start ------------------------------------------------

def test_stationary_start_cuts_the_first_clients_outputs_and_no_other():
    whole = {k: v for k, v in ROLLOUT.items() if k != "start"}
    cut, full = _take(ROLLOUT, 1), _take(whole, 1)
    assert [len(r["prompt"]) for r in cut] == [len(r["prompt"]) for r in full]
    for i, (c, f) in enumerate(zip(cut, full)):
        if i < CLIENTS:
            want = max(1, round(f["max_tokens"] * (i + 0.5) / CLIENTS))
            assert c["max_tokens"] == want
            assert 1 <= c["max_tokens"] <= f["max_tokens"]
        else:
            assert c["max_tokens"] == f["max_tokens"]
    # spread: the first is nearly done, the last has nearly all to go
    assert cut[0]["max_tokens"] * 8 < full[0]["max_tokens"] + 8
    assert cut[CLIENTS - 1]["max_tokens"] * 16 >= \
        full[CLIENTS - 1]["max_tokens"] * 15


def test_stationary_output_never_falls_under_one_token():
    assert request_stream.stationary_output(1, 0, 16) == 1
    assert request_stream.stationary_output(64, 0, 16) == 2
    assert request_stream.stationary_output(768, 15, 16) == 744


def test_an_unknown_start_is_refused():
    with pytest.raises(ValueError, match="unknown start"):
        _take(spec.merged(ROLLOUT, {"start": "cold"}), 1, 1)


# (c) the window closes on a token count ----------------------------------

class _Sample:
    def __init__(self, times):
        self.token_times = times


def _bursts(lanes, n, every=0.1, first=0.05, skew=0.0001):
    """`lanes` streams, a burst of one token each every `every` seconds."""
    return [_Sample([first + every * k + skew * i for k in range(n)])
            for i in range(lanes)]


@pytest.mark.parametrize("tokens, burst", [(1, 0), (4, 0), (5, 1), (17, 4),
                                           (20, 4), (21, 5)])
def test_the_window_closes_with_the_burst_that_holds_the_nth_token(tokens,
                                                                   burst):
    streams = _bursts(4, 30)
    t1, short = client.close_on_tokens(streams, 0.0, tokens, cap=2.5)
    assert short == 0
    # the burst's last token, read whole and nothing of the next
    assert abs(t1 - (0.05 + 0.1 * burst + 0.0003)) < 1e-9


def test_out_tok_s_counts_whole_bursts_of_a_window_closed_on_tokens():
    streams = _bursts(4, 30)
    t1, _ = client.close_on_tokens(streams, 0.0, 38, cap=2.5)   # burst 9
    tokens, seconds = client.out_tok_s(streams, 0.0, t1)
    # bursts 1..9 after the first, over 0.9 s: the rate of the stream
    assert tokens == 36 and abs(seconds - 0.9) < 1e-3
    # the same count whichever token of the last burst was the n-th
    for n in (37, 39, 40):
        assert client.close_on_tokens(streams, 0.0, n, cap=2.5)[0] == t1


def test_a_slow_step_inside_the_window_moves_the_end_and_not_the_count():
    fast = _bursts(4, 30)
    slow = [_Sample([t if k < 5 else t + 0.7
                     for k, t in enumerate(s.token_times)]) for s in fast]
    (t_fast, _), (t_slow, _) = (client.close_on_tokens(x, 0.0, 38, cap=5.0)
                                for x in (fast, slow))
    assert abs((t_slow - t_fast) - 0.7) < 1e-9
    counts = [client.out_tok_s(x, 0.0, t)[0]
              for x, t in ((fast, t_fast), (slow, t_slow))]
    assert counts == [36, 36]


def test_the_cap_closes_a_window_that_is_short_and_says_by_how_much():
    streams = _bursts(4, 30)
    t1, short = client.close_on_tokens(streams, 0.0, 100, cap=1.0)
    assert t1 == 1.0 and short == 100 - 40
    read = spec.load_reader("per_layer", "window.tokens_short").read
    assert read(SimpleNamespace(tokens_short=short)) == 60
    assert read(SimpleNamespace(tokens_short=0)) == 0
    assert read(SimpleNamespace(tokens_short=None)) is None   # time window
    # a slower program reads a lower rate, not a failure
    tokens, seconds = client.out_tok_s(streams, 0.0, t1)
    assert tokens == 36 and abs(seconds - 0.9) < 1e-3


def test_tokens_read_before_the_window_opened_do_not_count():
    streams = _bursts(4, 30)
    t1, short = client.close_on_tokens(streams, 1.0, 8, cap=3.0)
    assert short == 0 and abs(t1 - (1.15 + 0.0003)) < 1e-9


# (d) the steps as counts -------------------------------------------------

def _window(mix, seed):
    return loop_steps.window(
        request_stream.stream(mix, seed, 50257), mix["loop"]["clients"],
        mix["ramp"]["first_tokens"], mix["window"]["tokens"])


@pytest.mark.parametrize("seeds", [(1, 2), (11, 3000000011)])
def test_two_seeds_give_the_same_sequence_of_steps(seeds):
    a, b = (_window(ROLLOUT, s) for s in seeds)
    assert a == b


def test_without_the_key_two_seeds_give_other_steps():
    free = {k: v for k, v in ROLLOUT.items() if k != "lengths_seed"}
    kinds = lambda w: [s["kind"] for s in w[1]]   # noqa: E731
    assert kinds(_window(free, 1)) != kinds(_window(free, 2))


def test_the_window_holds_the_jobs_share_of_chunk_steps():
    ramp, held = _window(ROLLOUT, 1)
    got = loop_steps.count(held)
    # every step emits a token a lane: the count is the mix's, to a burst
    want = ROLLOUT["window"]["tokens"]
    assert want <= got["tokens"] < want + CLIENTS
    assert got["mixed"] >= 5 and got["done"] >= 4
    share = got["mixed"] / (got["mixed"] + got["decode"])
    long = loop_steps.count(loop_steps.window(
        request_stream.stream(ROLLOUT, 1, 50257), CLIENTS, CLIENTS, 40000)[1])
    steady = long["mixed"] / (long["mixed"] + long["decode"])
    assert abs(share - steady) < 0.03
    # the ramp is the first prompts' chunks and nothing else
    longest = max(len(r["prompt"]) for r in _take(ROLLOUT, 1, CLIENTS))
    assert [s["kind"] for s in ramp] == ["mixed"] * -(-longest // 128)


def test_a_chunk_step_reads_128_prompt_tokens_a_lane():
    reqs = iter([{"prompt": [0] * 300, "max_tokens": 2},
                 {"prompt": [0] * 5, "max_tokens": 4}] +
                [{"prompt": [0] * 129, "max_tokens": 1}] * 4)
    run = loop_steps.steps(reqs, clients=2)
    got = [next(run) for _ in range(7)]
    assert [s["kind"] for s in got] == ["mixed", "mixed", "mixed", "decode",
                                        "decode", "mixed", "decode"]
    assert [s["tokens"] for s in got] == [1, 1, 2, 2, 0, 0, 2]
    assert [s["done"] for s in got] == [0, 0, 0, 2, 0, 0, 2]
