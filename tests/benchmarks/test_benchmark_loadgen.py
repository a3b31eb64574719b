"""The load generator's plan and the arithmetic from records to metrics."""

from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
import json
import os
import sys
import threading
import time
from types import SimpleNamespace

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


# -- the closed loop against a stub server ------------------------------------

class _Stub(BaseHTTPRequestHandler):
    """Two token events and the terminal event; request i answers after
    30 ms x (i % 4), so a wave's requests end at different times."""

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.end_headers()
        time.sleep(0.03 * (body["seed"] % 4))
        try:
            for frame in ({"tokens": [1]}, {"tokens": [2]}, {"done": True}):
                self.wfile.write(b"data: " + json.dumps(frame).encode()
                                 + b"\n\n")
                self.wfile.flush()
        except OSError:
            pass          # the window ended and the client hung up

    def log_message(self, *args):
        pass


@pytest.fixture()
def stub_port():
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()
    thread.join(timeout=10)


def _closed(port, monkeypatch, clients, n_plan, seconds, wave=False,
            **how):
    """run_closed or run_waves to its end; the records by request index."""
    monkeypatch.setattr(loadgen, "STAGGER_S", 0.1)
    plan = [{"i": i, "due": None, "prompt": [1, 2], "max_new_tokens": 2}
            for i in range(n_plan)]
    args = SimpleNamespace(port=port, tag="t", request_timeout=10.0,
                           t0=time.monotonic() + 0.05, seconds=seconds)
    out, lock = [], threading.Lock()
    run = loadgen.run_waves if wave else loadgen.run_closed
    threads = run(plan, args, out, lock, clients, **how)
    for t in threads:
        t.join(timeout=20)
    assert not any(t.is_alive() for t in threads)
    return sorted(out, key=lambda r: r["i"])


def test_a_wave_is_sent_together_and_only_after_the_wave_before_it(
        stub_port, monkeypatch):
    clients = 4
    recs = _closed(stub_port, monkeypatch, clients, 400, 0.8, wave=True)
    assert all(r["ok"] for r in recs) and len(recs) >= 3 * clients
    assert [r["i"] for r in recs] == list(range(len(recs)))
    assert len(recs) % clients == 0          # whole waves, up to the last
    waves = [recs[n:n + clients] for n in range(0, len(recs), clients)]
    # No stagger: a staggered start would spread the first sends by 0.3 s.
    for wave in waves:
        sent = [r["sent"] for r in wave]
        assert max(sent) - min(sent) < 0.15
    assert min(r["sent"] for r in waves[0]) >= 0.0
    # The answers end 0-90 ms apart, and nobody sends before the last did.
    for before, after in zip(waves, waves[1:]):
        done = [r["done"] for r in before]
        assert max(done) - min(done) > 0.05
        assert min(r["sent"] for r in after) >= max(done)


def test_with_a_lead_a_wave_s_first_request_goes_ahead_of_the_others(
        stub_port, monkeypatch):
    """`lead_ms`: client 0 sends when the wave is decided, the others the
    lead after it and together, so that an idle lane's first tick holds
    one request whatever the race between their sends would have given."""
    clients, lead = 4, 0.08
    recs = _closed(stub_port, monkeypatch, clients, 400, 0.9, wave=True,
                   lead_s=lead)
    assert all(r["ok"] for r in recs) and len(recs) >= 3 * clients
    waves = [recs[n:n + clients] for n in range(0, len(recs), clients)]
    for wave in waves:
        first, others = wave[0]["sent"], [r["sent"] for r in wave[1:]]
        assert lead - 0.005 <= min(others) - first < lead + 0.05
        assert max(others) - min(others) < 0.05
    for before, after in zip(waves, waves[1:]):
        assert after[0]["sent"] >= max(r["done"] for r in before)


def test_without_the_key_each_client_sends_when_its_own_last_completed(
        stub_port, monkeypatch):
    clients = 4
    recs = _closed(stub_port, monkeypatch, clients, 400, 0.8)
    assert all(r["ok"] for r in recs) and len(recs) > 3 * clients
    # The clients start STAGGER_S (here 0.1 s) apart: until then the first
    # is alone, one request after the other ...
    alone = [r for r in recs if r["sent"] < 0.09]
    assert alone and alone[0]["sent"] >= 0.0
    for a, b in zip(alone, alone[1:]):
        assert b["sent"] >= a["done"]
    # ... and once all have started nobody waits for anybody: a request is
    # sent while the other clients are inside theirs.
    others = [sum(o["sent"] < r["sent"] and (o["cut"] or r["sent"] < o["done"])
                  for o in recs) for r in recs if r["sent"] > 0.35]
    assert others and max(others) >= 2


@pytest.mark.parametrize("wave", [False, True])
def test_a_plan_that_runs_out_fails_the_run_and_ends_every_client(
        stub_port, monkeypatch, wave):
    recs = _closed(stub_port, monkeypatch, 4, 8, 5.0, wave=wave)
    assert sum(r["ok"] for r in recs) == 8
    bad = [r for r in recs if not r["ok"]]
    assert bad and all("plan ran out" in r["error"] for r in bad)


@pytest.mark.parametrize("lead_ms", [None, 60])
def test_main_reads_the_wave_from_the_traffic_file(stub_port, tmp_path,
                                                   capsys, lead_ms):
    traffic = {"loop": "closed", "clients": 4, "wave": True, "block": 4,
               "pool": 100,
               "prompt_tokens": {"dist": "uniform", "min": 2, "max": 6},
               "output_tokens": {"dist": "fixed", "value": 2}}
    if lead_ms is not None:
        traffic["lead_ms"] = lead_ms
    (tmp_path / "t.json").write_text(json.dumps(traffic))
    out = tmp_path / "records.jsonl"
    assert loadgen.main([
        "--traffic", str(tmp_path / "t.json"), "--seed", str(2**31 + 3),
        "--port", str(stub_port), "--seconds", "0.5", "--vocab", "50",
        "--t0", repr(time.monotonic() + 0.05), "--out", str(out),
        "--drain", "5"]) == 0
    assert json.loads(capsys.readouterr().out)["failed"] == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    waves = [recs[n:n + 4] for n in range(0, len(recs), 4)]
    assert len(waves) >= 2
    for before, after in zip(waves, waves[1:]):
        assert min(r["sent"] for r in after) >= max(
            r["done"] for r in before)
    ahead = [min(r["sent"] for r in w[1:]) - w[0]["sent"] for w in waves]
    if lead_ms is None:
        assert max(ahead) < 0.05
    else:
        assert min(ahead) >= lead_ms / 1e3 - 0.005


def test_a_wave_of_the_batch_cell_cannot_reach_the_p95_s_edge():
    """What keeps gpt2-large.batch's `itl_p95_ms` on a decode tick, with no
    tick's length in it (PERF.md, PR 35). Every wave is one block of the
    plan, so its prompts are the same 1536 tokens. Its first request goes
    `lead_ms` ahead, so the idle lane's first tick holds that prompt alone
    and delays nobody: no row decodes yet. A later prefill tick takes the
    lane's token budget less one token a decoding row, at least 256 - 31,
    so the other 31 prompts take at most 7 ticks more, and six would not
    hold them even with no row decoding but the first. Each such tick
    delays at most the wave's other 31 rows by one sample."""
    traffic = _traffic("batch")
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        serving = json.load(f)["serving"]
    clients, budget = traffic["clients"], serving["gen_prefill_chunk"]
    assert traffic["wave"] is True and traffic["block"] == clients
    assert traffic["lead_ms"] > 0
    assert serving["gen_max_batch_size"] == clients
    plan = loadgen.build_plan(traffic, 3, 51.0, 50257)
    outputs = traffic["output_tokens"]["value"]
    for n in range(0, len(plan), clients):
        wave = plan[n:n + clients]
        assert all(i["max_new_tokens"] == outputs for i in wave)
        assert sum(len(i["prompt"]) for i in wave) == 1536
        rest = sum(len(i["prompt"]) for i in wave[1:])
        later_ticks = -(-rest // (budget - (clients - 1)))
        assert later_ticks == 7 and rest > 5 * (budget - 1)
        spanning = later_ticks * (clients - 1)
        assert spanning / (clients * (outputs - 1)) < 0.027


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


def test_a_slipped_tick_moves_the_median_by_a_tick_and_the_mean_by_little():
    """docqa's TTFTs are quantised to ticks of ~108 ms, eight ticks or so
    (PERF.md, PR 35). One request in ten slipping a tick is what a run does
    from nothing: the median of such a population jumps by the whole tick,
    the mean by a tenth of one."""
    tick, n = 108.0, 115

    def window(slipped):
        ticks = [8] * 58 + [9] * 57       # the median sits on an edge
        for k in range(0, slipped):
            ticks[10 * k + 5] += 1        # one in ten of them, spread out
        return [_rec(float(i), float(i) + t * tick / 1e3,
                     [[float(i) + t * tick / 1e3, 1]])
                for i, t in enumerate(ticks)]

    steady = metrics.end_to_end(window(0), 200.0, 1.0)
    slipped = metrics.end_to_end(window(n // 10), 200.0, 1.0)
    p50 = [m["ttft_p50_ms"]["value"] for m in (steady, slipped)]
    mean = [m["ttft_mean_ms"]["value"] for m in (steady, slipped)]
    assert p50[1] - p50[0] == pytest.approx(tick)          # 12.5 %
    assert 0 < mean[1] / mean[0] - 1 < 0.02
    assert mean[1] - mean[0] == pytest.approx(tick * (n // 10) / n)
