"""The load generator's plan and the arithmetic from records to metrics."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import BENCH  # noqa: E402

from lib import loadgen, metrics  # noqa: E402

TRAFFIC = sorted(name[:-5] for name in os.listdir(os.path.join(BENCH,
                                                               "traffic")))


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_plan(name):
    a = loadgen.build_plan(_traffic(name), 2**31 + 5, 20.0, 50257)
    b = loadgen.build_plan(_traffic(name), 2**31 + 5, 20.0, 50257)
    assert a == b
    assert all(0 <= t < 50257 for item in a[:50] for t in item["prompt"])


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_seed_does_the_same_work_with_other_tokens(name, monkeypatch):
    traffic = _traffic(name)
    a = loadgen.build_plan(traffic, 1, 20.0, 1000)
    b = loadgen.build_plan(traffic, 2, 20.0, 1000)
    shape = lambda plan: [(i["due"], len(i["prompt"]), i["max_new_tokens"])
                          for i in plan]
    assert shape(a) == shape(b)
    assert [i["prompt"] for i in a] != [i["prompt"] for i in b]
    # Another order is another experiment: no file and no seed chooses it.
    monkeypatch.setattr(loadgen, "ORDER_SEED", 1)
    c = loadgen.build_plan(traffic, 1, 20.0, 1000)
    assert shape(c) != shape(a)
    for k in (1, 2):
        assert sorted(x[k] for x in shape(c)) == sorted(x[k] for x in shape(a))


@pytest.mark.parametrize("name", [n for n in TRAFFIC
                                  if _traffic(n)["loop"] == "closed"])
def test_each_block_of_a_closed_loop_covers_the_distributions_anew(name):
    traffic = _traffic(name)
    plan = loadgen.build_plan(traffic, 1, 20.0, 1000)
    block = traffic["block"]
    lens = [(len(i["prompt"]), i["max_new_tokens"]) for i in plan]
    first, second = lens[:block], lens[block:2 * block]
    for k in (0, 1):
        assert sorted(x[k] for x in first) == sorted(x[k] for x in second)


@pytest.mark.parametrize("name", TRAFFIC)
def test_lengths_stay_inside_the_file_s_limits(name):
    traffic = _traffic(name)
    plan = loadgen.build_plan(traffic, 9, 20.0, 1000)
    for key, length in (("prompt_tokens", lambda i: len(i["prompt"])),
                        ("output_tokens", lambda i: i["max_new_tokens"])):
        dist = traffic[key]
        lo = dist.get("min", dist.get("value"))
        hi = dist.get("max", dist.get("value"))
        assert all(lo <= length(i) <= hi for i in plan)


def test_open_loop_schedule_has_the_rate_and_the_same_gaps_for_every_seed():
    import random

    a = loadgen.arrival_times(3.0, 40.0, random.Random(1))
    b = loadgen.arrival_times(3.0, 40.0, random.Random(2))
    assert len(a) == len(b) == 120
    assert a[0] == 0.0 and a == sorted(a) and a[-1] < 40.0
    gaps = lambda t: sorted(round(y - x, 9) for x, y in zip(t, t[1:] + [40.0]))
    assert gaps(a) == gaps(b) and a != b
    # Exponential gaps: the median gap is ln 2 of the mean gap.
    assert gaps(a)[60] == pytest.approx(0.693 / 3.0, rel=0.05)


def test_lognormal_quantiles_have_the_file_s_median_and_clip():
    dist = {"dist": "lognormal", "median": 128, "sigma": 0.7,
            "min": 32, "max": 512}
    assert loadgen.quantile(dist, 0.5) == 128
    assert loadgen.quantile(dist, 0.0001) == 32
    assert loadgen.quantile(dist, 0.9999) == 512
    with pytest.raises(ValueError):
        loadgen.quantile({"dist": "zipf"}, 0.5)


def test_shared_prefixes_are_data_not_code():
    traffic = dict(_traffic("chat"), sharing={
        "share": 1.0, "groups": 2, "min_suffix": 16,
        "prefix_tokens": {"dist": "fixed", "value": 64}})
    plan = loadgen.build_plan(traffic, 4, 10.0, 1000)
    heads = {tuple(item["prompt"][:64]) for item in plan}
    assert len(heads) == 2
    assert all(len(item["prompt"]) >= 64 + 16 for item in plan)


# -- records -> metrics -------------------------------------------------------

def _rec(due, first, events, prompt=10, ok=True, sent=None, cut=False):
    return {"i": 0, "due": due, "sent": due if sent is None else sent,
            "first": first, "done": events[-1][0] if events else None,
            "events": events, "prompt_tokens": prompt,
            "max_new_tokens": sum(n for _, n in events), "ok": ok,
            "error": None if ok else "x", "cut": cut}


def test_latency_counts_from_when_the_request_was_due():
    late = _rec(due=1.0, first=1.5, events=[[1.5, 1]], sent=1.3)
    assert metrics.ttft_ms([late]) == [pytest.approx(500.0)]
    assert metrics.lateness_ms([late])["max"] == pytest.approx(300.0)


def test_a_failed_request_has_no_latency_and_counts_as_failed():
    good = _rec(0.0, 0.2, [[0.2, 1], [0.3, 1]])
    bad = _rec(0.0, None, [], ok=False)
    assert metrics.counts([good, bad]) == (2, 1)
    assert len(metrics.ttft_ms([good, bad])) == 1
    assert len(metrics.itl_ms([good, bad])) == 1


def test_gap_between_tokens_is_the_event_gap_over_its_tokens():
    r = _rec(0.0, 0.1, [[0.1, 1], [0.2, 1], [0.5, 3]])
    assert metrics.itl_ms([r]) == pytest.approx([100.0, 100.0, 100.0, 100.0])


def test_tokens_are_counted_where_the_window_cuts_them():
    r = _rec(0.0, 0.5, [[0.5, 1], [1.5, 2], [2.5, 4]], prompt=10)
    assert metrics.window_tokens([r], 2.0) == 10 + 1 + 2
    assert metrics.window_tokens([r], 3.0) == 10 + 7
    before = _rec(-1.0, -0.5, [[-0.5, 1], [0.5, 1]], prompt=10)
    assert metrics.window_tokens([before], 2.0) == 1


@pytest.mark.parametrize("first,want", [
    (1.0, 100.0),     # sent and answered inside the window: all of it
    (1.96, 100.0), (2.04, 100.0 * 1.5 / 1.54),   # either side of the edge
    (3.5, 50.0),      # half of its wait lies inside
])
def test_a_prompt_counts_by_the_part_of_its_wait_inside_the_window(first,
                                                                   want):
    r = _rec(0.5, first, [], prompt=100)
    assert metrics.window_tokens([r], 2.0) == pytest.approx(want)


@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 75, 3), ([1, 2, 3, 4], 100, 4),
    ([5], 95, 5), (list(range(1, 101)), 90, 90), (list(range(1, 101)), 95, 95),
])
def test_percentile_is_nearest_rank(values, p, want):
    assert metrics.percentile(values, p) == want


@pytest.mark.parametrize("n,want", [
    (5, None), (20, 50), (100, 90), (135, 90), (200, 95), (1000, 99),
    (10001, 99.9),
])
def test_highest_percentile_keeps_ten_samples_beyond(n, want):
    assert metrics.highest_percentile(n) == want


def test_mean_ttft_weighs_every_request_alike_from_its_due_time():
    recs = [_rec(0.0, 0.25, [[0.25, 1]]), _rec(1.0, 1.75, [[1.75, 1]]),
            _rec(2.0, None, [], ok=False)]
    out = metrics.end_to_end(recs, 4.0, 1.0)
    assert out["ttft_mean_ms"] == {"value": pytest.approx(500.0),
                                   "unit": "ms"}
    assert "ttft_mean_ms" not in metrics.end_to_end(recs[2:], 4.0, 1.0)


def test_end_to_end_names_units_and_missing_samples():
    r = _rec(0.0, 0.25, [[0.25, 1], [0.35, 1]], prompt=8)
    out = metrics.end_to_end([r], 2.0, 12.5)
    assert out["setup_s"] == {"value": 12.5, "unit": "s"}
    assert out["tokens_per_s"]["value"] == pytest.approx(5.0)
    assert out["ttft_p50_ms"]["value"] == pytest.approx(250.0)
    assert out["itl_p95_ms"]["unit"] == "ms"
    assert "ttft_p50_ms" not in metrics.end_to_end(
        [_rec(0.0, None, [], ok=False)], 2.0, 1.0)
