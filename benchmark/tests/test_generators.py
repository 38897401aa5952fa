"""The generators give the same traffic for one seed and other traffic for
another, and what they give has the shape the mix asks for."""
import itertools
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmark import spec  # noqa: E402
from benchmark.generators import packed_docs, request_stream  # noqa: E402

TRAFFIC = spec.HERE / "traffic"


def _take(mix, seed, n=200):
    mix = spec.load_json(TRAFFIC / f"{mix}.json")
    return mix, list(itertools.islice(
        request_stream.stream(mix, seed, 50257), n))


def test_same_seed_same_requests_other_seed_other_requests():
    for name in ("rollout", "docqa", "chat"):
        _, a = _take(name, 7)
        _, b = _take(name, 7)
        _, c = _take(name, 8)
        assert a == b, name
        assert [r["prompt"] for r in a] != [r["prompt"] for r in c], name


def test_lengths_stay_inside_the_mix_and_the_model():
    for name in ("rollout", "docqa", "chat"):
        mix, reqs = _take(name, 3, 400)
        # a stationary start cuts the first `clients` outputs, down to 1
        cut = mix["loop"]["clients"] if mix.get("start") else 0
        for i, r in enumerate(reqs):
            own = len(r["prompt"]) - r["shared_tokens"] \
                if mix.get("prefix") else len(r["prompt"])
            assert own >= mix["prompt"]["min"]
            assert (1 if i < cut else mix["output"]["min"]) \
                <= r["max_tokens"] <= mix["output"]["max"]
            assert len(r["prompt"]) + r["max_tokens"] <= 2048


def test_docqa_shares_each_document_four_times_interleaved_by_eight():
    mix, reqs = _take("docqa", 5, 64)
    group, uses = mix["prefix"]["group"], mix["prefix"]["uses"]
    first = reqs[:group * uses]
    for i, r in enumerate(first):
        doc = first[i % group]
        n = r["shared_tokens"] if i >= group else 0
        assert (i < group) == (r["shared_tokens"] == 0)
        assert r["prompt"][:n] == doc["prompt"][:n]
        assert mix["prefix"]["length"]["min"] <= (n or 1024)
    # the next group's documents are new
    assert reqs[group * uses]["shared_tokens"] == 0


def test_open_loop_arrivals_follow_the_rate_and_the_bursts():
    mix, _ = _take("chat", 1, 1)
    mix = spec.merged(mix, {"loop": {"rate_rps": 20.0}})
    due = [r["due_s"] for r in itertools.islice(
        request_stream.stream(mix, 1, 1000), 4000)]
    assert due == sorted(due)
    assert abs(len(due) / due[-1] - 20.0) < 1.5
    burst = spec.merged(mix, {"loop": {"arrivals": "onoff", "on_s": 2.0,
                                       "off_s": 3.0}})
    due = [r["due_s"] for r in itertools.islice(
        request_stream.stream(burst, 1, 1000), 2000)]
    assert all(t % 5.0 < 2.0 + 1e-9 for t in due)


def test_packed_batches_are_full_rows_shifted_by_one_and_seeded():
    mix = spec.load_json(TRAFFIC / "packed-2k.json")
    take = lambda seed: list(itertools.islice(  # noqa: E731
        packed_docs.batches(mix, seed, 50257, 4, 2048), 3))
    a, b, c = take(1), take(1), take(2)
    for (ia, la), (ib, lb) in zip(a, b):
        assert ia.shape == la.shape == (4, 2048) and ia.dtype == np.int32
        assert (ia == ib).all() and (la == lb).all()
        assert (ia[:, 1:] == la[:, :-1]).all()
        assert ia.min() >= 0 and ia.max() < 50257
    assert not (a[0][0] == c[0][0]).all()
    # documents end in the end-of-text id, about one every 700 tokens
    ends = sum(int((i == 50256).sum()) for i, _ in a)
    assert 10 <= ends <= 80
