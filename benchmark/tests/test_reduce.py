"""The reduction from traces and clocks to metrics, on hand-made intervals
whose answers can be read off, and on a recorded chip capture."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.reduce import client, intervals as iv, kernels  # noqa: E402
from benchmark.reduce.stats import median, quantile  # noqa: E402

# a device timeline in ns: compute 0-40 and 60-100, a collective 30-70
COMPUTE = [(0, 40, "fusion.1"), (60, 100, "fusion.2")]
COLLECTIVE = [(30, 70, "all-reduce.3")]


def test_busy_merges_overlaps_and_gaps_are_what_is_left():
    ops = COMPUTE + COLLECTIVE + [(120, 130, "copy.4")]
    assert iv.busy(ops) == 110
    assert iv.gaps(ops, 0, 150) == [(100, 120), (130, 150)]
    assert iv.gaps(ops, 50, 125) == [(100, 120)]
    assert iv.busy(iv.clip(ops, 35, 65)) == 30


def test_exposed_is_the_collective_time_no_compute_hides():
    assert iv.exposed(COLLECTIVE, COMPUTE) == 20      # 40-60 of 30-70
    assert iv.exposed(COLLECTIVE, []) == 40
    assert iv.exposed(COLLECTIVE, [(0, 100, "x")]) == 0


def test_collectives_are_told_by_their_own_name():
    from benchmark.reduce.xplane import is_collective

    assert is_collective("%all-reduce-start.7 = (f32[8]) all-reduce-start(")
    assert is_collective("%all-gather.5 = bf16[8,4] all-gather(bf16[2,4] %p)")
    assert is_collective("%reduce-scatter.1 = f32[2] reduce-scatter(%x)")
    assert not is_collective(
        "%fusion.3 = f32[8] fusion(f32[8] %all-gather.5), kind=kLoop")
    assert not is_collective("%reduce.4 = f32[] reduce(f32[8] %x)")


def test_names_fold_to_categories_and_seconds_add_up():
    assert iv.category("%fusion.123 = bf16[8]{0} fusion(...)") == "fusion"
    assert iv.category("all-reduce-start.7") == "all-reduce-start"
    by = iv.seconds_by(COMPUTE + COLLECTIVE, ns=1.0)
    assert by == {"fusion": 80.0, "all-reduce": 40.0}


def test_quantiles_are_nearest_rank():
    xs = list(range(1, 101))
    assert median(xs) == 50 and quantile(xs, 95) == 95
    assert quantile(xs, 99) == 99 and quantile([], 50) is None
    assert median([3.0]) == 3.0


class _Sample:
    def __init__(self, times, sent=0.0, due=None):
        self.token_times, self.sent, self.due = times, sent, due


def test_out_tok_s_counts_whole_bursts():
    # 4 streams, a burst of 4 tokens every 0.1 s from 0.05 on; the window
    # 0.0-1.0 holds the bursts at 0.05 .. 0.95 and cuts nothing
    streams = [_Sample([0.05 + 0.1 * k + 0.0001 * i for k in range(20)])
               for i in range(4)]
    tokens, seconds = client.out_tok_s(streams, 0.0, 1.0)
    assert tokens == 36 and abs(seconds - 0.9) < 1e-3
    # an edge inside a burst neither adds nor drops part of it
    tokens, seconds = client.out_tok_s(streams, 0.05015, 1.0502)
    assert tokens / seconds == 40.0 or abs(tokens / seconds - 40.0) < 0.05


def test_gaps_and_ttfts_stay_inside_the_window():
    s = _Sample([1.0, 1.5, 2.5, 4.0], sent=0.2)
    assert client.itl_gaps([s], 1.2, 3.0) == [1.0]
    assert client.ttfts([s], 0.0, 2.0) == [0.8]
    assert client.ttfts([s], 1.1, 2.0) == []
    late = _Sample([3.0], sent=1.2, due=1.0)
    assert client.ttfts([late], 0.0, 5.0) == [2.0]
    assert abs(client.lateness([late], 0.0, 5.0)[0] - 0.2) < 1e-12


def test_kernel_bounds_from_shapes():
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = kernels.ragged_decode_call(16 * 1024, 2048, 2)
    assert nbytes == 2 * 16 * 1024 * 2048 * 2
    seconds, which = kernels.least_seconds(flops, nbytes, peak)
    assert which == "bandwidth" and abs(seconds - nbytes / 819e9) < 1e-12
    flops, nbytes = kernels.flash_train_layer(8, 2048, 1536, 2)
    assert flops == 6 * 8 * 2048 * 2048 * 1536
    assert kernels.least_seconds(flops, nbytes, peak)[1] == "compute"


def test_ramp_and_live_kv_are_read_where_there_is_something_to_read():
    from types import SimpleNamespace

    from benchmark import spec

    read = lambda name, **kw: spec.load_reader(  # noqa: E731
        "per_layer", name).read(SimpleNamespace(**kw))
    assert read("setup.ramp_s", load_start=10.0, t0=17.5) == 7.5
    assert read("setup.ramp_s", load_start=None, t0=17.5) is None   # training
    assert read("pool.live_kv_share",
                gauges={"block_utilization": 0.125}) == 12.5
    assert read("pool.live_kv_share", gauges=None) is None
    assert read("pool.live_kv_share", gauges={}) is None
