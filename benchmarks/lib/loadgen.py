#!/usr/bin/env python3
"""The load generator: a stdlib-only child process of benchmarks/run.py.

It imports neither jax nor tpu_engine, so it holds no chip and shares no
interpreter lock with the server. It reads one traffic file (a data file of
parameters, benchmarks/traffic/<mix>.json), draws the whole plan up front
from the seed, sends it over HTTP to the gateway's /generate/stream, and
writes one JSON line per request.

Every seed gives the SAME requests at the SAME times: prompt lengths, output
lengths and (open loop) inter-arrival gaps are the quantiles of the file's
distributions, in ONE order (ORDER_SEED, a constant of this file: no traffic
file chooses its own). The seed draws only the token values. This is a fixed
schedule of exponential gaps, not a Poisson process drawn anew. The order is
part of the work: which requests come late decides how much of them the
window still sees, and in a closed loop who queues behind whom. Measured on
the chip with the order drawn from the seed, two runs of one seed agreed to
0.01% in tokens per second and six seeds spread by 5.5% (PERF.md, PR 24):
another order is another experiment, not another sample of the same one, and
the bounds do not cover it. A closed loop's clients start STAGGER_S apart, so
that the order in which the server sees the first requests is not a race
between threads. Every request is greedy (temperature 0).

A closed loop whose file says `"wave": true` sends in waves: its clients send
together, with no stagger, and each sends its next request only when ALL of
the wave have completed, as a job that calls generate on a batch of prompts
and then on the next. Client k's n-th request is the plan's (n * clients +
k)-th, so a wave is one block of the plan. What the file fixes by this is
where a wave's prefill ticks fall: together, at the wave's start, however
long any tick takes (PERF.md, PR 35). With `"lead_ms": m` client 0 sends
at the wave's start and the others m milliseconds after it: an idle lane
begins its first tick on the first request it sees, so without a lead how
many of a wave's requests that tick holds, and with it how many ticks the
wave's prompts take, is a race between the clients' sends and the lane's
wake-up; with it the first tick holds one request, every time.

Clock: time.monotonic(), which on Linux is one clock for every process of
the machine; the parent hands over the window's start on that clock.

Open loop: arrival times are fixed before the first request is sent, and a
request's latency counts from when it was DUE, so a stall is charged to
every request it delays. How late the generator itself ran (sent - due) is
reported. Closed loop: N clients, each sends its next request when its last
one completed; a request is due when it is sent. At the window's end a
closed-loop client hangs up on the request it is in: what that request
streamed until then counts, and it is marked `cut`, not failed.
"""

import argparse
import http.client
import json
import math
import random
from statistics import NormalDist
import sys
import threading
import time

LOOPS = ("open", "closed")
ORDER_SEED = 0      # the one order of sizes and gaps, for every file and seed
STAGGER_S = 0.020   # between the starts of a closed loop's clients


# -- the plan -----------------------------------------------------------------

def quantile(dist, u):
    """The u-quantile (0 < u < 1) of a length distribution, as an int."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        value = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = NormalDist().inv_cdf(u)
        value = dist["median"] * math.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return int(round(min(max(value, dist["min"]), dist["max"])))


def stratified(dist, n, rng, block):
    """n draws that cover `dist` evenly: the quantiles at (i + 0.5) / block
    for each block of `block` draws, each block shuffled on its own — so
    any run of consecutive draws covers the distribution, whichever seed
    ordered it."""
    out = []
    while len(out) < n:
        size = min(block, n - len(out))
        part = [quantile(dist, (i + 0.5) / size) for i in range(size)]
        rng.shuffle(part)
        out.extend(part)
    return out


def arrival_times(rate_per_s, seconds, rng):
    """Open loop: round(rate * seconds) arrivals inside [0, seconds). The
    gaps are the quantiles of the exponential distribution (a Poisson
    process's gaps) scaled to fill the window, in an order drawn from
    `rng`."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(gaps)
    rng.shuffle(gaps)
    times, t = [], 0.0
    for g in gaps:
        times.append(t)          # first arrival at 0, last before `seconds`
        t += g * scale
    return times


def build_plan(traffic, seed, seconds, vocab):
    """The whole run as a list of requests: index, due time (open loop) or
    None (closed loop), prompt tokens, output tokens. Pure: the same
    (traffic, seed, seconds, vocab) gives the same plan."""
    loop = traffic["loop"]
    if loop not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, not {loop!r}")
    rng = random.Random(seed)                                # token values
    order = random.Random(ORDER_SEED)                        # sizes, gaps
    if loop == "open":
        due = arrival_times(float(traffic["rate_per_s"]), seconds, order)
        n = len(due)
        block = n
    else:
        clients = int(traffic["clients"])
        block = int(traffic.get("block", max(8, clients)))
        # More than any window can complete; the clients stop at its end.
        n = int(traffic.get("pool", 64)) * block
        due = [None] * n
    prompt_lens = stratified(traffic["prompt_tokens"], n, order, block)
    output_lens = stratified(traffic["output_tokens"], n, order, block)

    sharing = traffic.get("sharing") or {}
    share = float(sharing.get("share", 0.0))
    prefixes = []
    if share > 0.0:
        groups = int(sharing["groups"])
        lens = stratified(sharing["prefix_tokens"], groups, rng, groups)
        prefixes = [[rng.randrange(vocab) for _ in range(m)] for m in lens]

    plan = []
    for i in range(n):
        prompt = []
        if prefixes and rng.random() < share:
            prompt = list(prefixes[rng.randrange(len(prefixes))])
        # A shared prefix leaves at least `min_suffix` tokens of its own.
        own = max(int(sharing.get("min_suffix", 16)) if prompt else 0,
                  prompt_lens[i] - len(prompt))
        prompt += [rng.randrange(vocab) for _ in range(own)]
        plan.append({"i": i, "due": due[i], "prompt": prompt,
                     "max_new_tokens": output_lens[i]})
    return plan


# -- one request --------------------------------------------------------------

def stream_request(port, rid, item, timeout_s, cutoff=None):
    """POST one /generate/stream; returns (first, events, done, error):
    monotonic times of the first token event, of every token event with
    its token count, and of the terminal event. Past `cutoff` the client
    hangs up after the next event; `done` is then the string "cut"."""
    body = json.dumps({"request_id": rid, "prompt_tokens": item["prompt"],
                       "max_new_tokens": item["max_new_tokens"],
                       "temperature": 0.0, "seed": item["i"]})
    events, done, error = [], None, None
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST", "/generate/stream", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return None, [], None, f"http {resp.status}: {resp.read(200)!r}"
        # http.client undoes the chunked framing; an SSE frame is one
        # "data: {...}" line and a blank line.
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            evt = json.loads(line[len(b"data: "):])
            if evt.get("done"):
                done = now
                if "error" in evt:
                    error = str(evt["error"])[:300]
                resp.read()      # the closing chunk: leave nothing unread
                break
            n_tok = len(evt.get("tokens", ()))
            if n_tok:
                events.append((now, n_tok))
            if cutoff is not None and now >= cutoff:
                done = "cut"
                break
    except (OSError, http.client.HTTPException, ValueError) as exc:
        error = f"{type(exc).__name__}: {exc}"[:300]
    finally:
        conn.close()
    if done is None and error is None:
        error = "stream ended without a terminal event"
    got = sum(n for _, n in events)
    if error is None and done != "cut" and got != item["max_new_tokens"]:
        error = f"{got} tokens streamed, {item['max_new_tokens']} asked"
    first = events[0][0] if events else None
    return first, events, done, error


def record(item, t0, due, sent, result):
    """One line of the records file; every time is relative to t0."""
    first, events, done, error = result
    cut, done = done == "cut", None if done == "cut" else done

    def rel(t):
        return None if t is None else round(t - t0, 6)

    return {"i": item["i"], "due": rel(due), "sent": rel(sent),
            "first": rel(first), "done": rel(done), "cut": cut,
            "events": [[rel(t), n] for t, n in events],
            "prompt_tokens": len(item["prompt"]),
            "max_new_tokens": item["max_new_tokens"],
            "ok": error is None, "error": error}


def failed(i, t0, due, error):
    """The record of a request that never got a reply."""
    return record({"i": i, "prompt": [], "max_new_tokens": 0}, t0, due, None,
                  (None, [], None, error))


# -- the two loops ------------------------------------------------------------

def run_open(plan, args, out, lock):
    """Send request i at t0 + due[i], each from a thread of its own, so a
    slow reply never delays a later send."""
    threads = []

    def one(item):
        sent = time.monotonic()
        result = stream_request(args.port, f"{args.tag}-{item['i']}", item,
                                args.request_timeout)
        with lock:
            out.append(record(item, args.t0, args.t0 + item["due"], sent,
                              result))

    for item in plan:
        delay = args.t0 + item["due"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one, args=(item,), daemon=True)
        t.start()
        threads.append(t)
    return threads


def send_closed(item, args, out, lock):
    """One request of a closed loop: due when it is sent, cut off at the
    window's end."""
    sent = time.monotonic()
    result = stream_request(args.port, f"{args.tag}-{item['i']}", item,
                            args.request_timeout,
                            cutoff=args.t0 + args.seconds)
    with lock:
        out.append(record(item, args.t0, sent, sent, result))


def plan_ran_out(plan, args, out, lock):
    with lock:
        out.append(failed(len(plan), args.t0, None, "the plan ran out before "
                          "the window ended: raise the traffic file's pool"))


def started(threads):
    for t in threads:
        t.start()
    return threads


def run_closed(plan, args, out, lock, clients):
    """`clients` threads, started STAGGER_S apart; each takes the plan's
    next request when its last completed, until the window ends."""
    cursor = iter(plan)
    end = args.t0 + args.seconds

    def client(k):
        delay = args.t0 + k * STAGGER_S - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < end:
            with lock:
                item = next(cursor, None)
            if item is None:       # the load fell short of the window
                return plan_ran_out(plan, args, out, lock)
            send_closed(item, args, out, lock)

    return started([threading.Thread(target=client, args=(k,), daemon=True)
                    for k in range(clients)])


def run_waves(plan, args, out, lock, clients, lead_s=0.0):
    """`clients` threads that meet before every request: they send
    together, client k the plan's (n * clients + k)-th request in wave n,
    and nobody sends before the last of the wave before completed. Whether
    the window is over is decided once a wave, for all of them. With a
    lead the others send `lead_s` after client 0 went ahead."""
    end = args.t0 + args.seconds
    go, ahead = {}, threading.Event()

    def decide():                  # run by one client while the rest wait
        ahead.clear()
        go["on"] = time.monotonic() < end

    meet = threading.Barrier(clients, action=decide)

    def client(k):
        delay = args.t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            for n in range(k, len(plan), clients):
                meet.wait()
                if not go["on"]:
                    return
                if k == 0:
                    ahead.set()
                elif lead_s > 0:
                    ahead.wait(timeout=10.0)
                    time.sleep(lead_s)
                send_closed(plan[n], args, out, lock)
        except threading.BrokenBarrierError:
            return
        meet.abort()               # the others must not wait for this one
        plan_ran_out(plan, args, out, lock)

    return started([threading.Thread(target=client, args=(k,), daemon=True)
                    for k in range(clients)])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True, help="traffic file (json)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True,
                    help="window start on time.monotonic()")
    ap.add_argument("--out", required=True, help="records file (jsonl)")
    ap.add_argument("--tag", default="w", help="request-id prefix")
    ap.add_argument("--drain", type=float, default=30.0,
                    help="seconds after the window to wait for replies")
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: use this rate, not the file's (sweep)")
    ap.add_argument("--cap-new-tokens", type=int, default=None,
                    help="cut every request's output to this (warm-up)")
    ap.add_argument("--cap-prompt-tokens", type=int, default=None,
                    help="cut every request's prompt to this (warm-up)")
    args = ap.parse_args(argv)

    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.rate is not None:
        traffic = dict(traffic, rate_per_s=args.rate)
    args.request_timeout = args.seconds + args.drain + 30.0
    plan = build_plan(traffic, args.seed, args.seconds, args.vocab)
    if args.cap_new_tokens is not None:
        for item in plan:
            item["max_new_tokens"] = min(item["max_new_tokens"],
                                         args.cap_new_tokens)
    if args.cap_prompt_tokens is not None:
        for item in plan:
            del item["prompt"][args.cap_prompt_tokens:]

    out, lock = [], threading.Lock()
    if traffic["loop"] == "open":
        threads = run_open(plan, args, out, lock)
    else:
        clients = int(traffic["clients"])
        if traffic.get("wave"):
            threads = run_waves(plan, args, out, lock, clients,
                                float(traffic.get("lead_ms", 0)) / 1e3)
        else:
            threads = run_closed(plan, args, out, lock, clients)
    limit = args.t0 + args.seconds + args.drain
    for t in threads:
        t.join(timeout=max(0.0, limit - time.monotonic()))
    with lock:
        done = list(out)
    # What has not been answered by the drain limit failed: in an open
    # loop every planned request not recorded, in a closed loop every
    # client still inside one (a request never started is not an attempt).
    late = "not completed by the drain limit"
    if traffic["loop"] == "open":
        seen = {r["i"] for r in done}
        done += [failed(item["i"], args.t0, args.t0 + item["due"], late)
                 for item in plan if item["i"] not in seen]
    else:
        done += [failed(-1 - k, args.t0, None, late)
                 for k, t in enumerate(threads) if t.is_alive()]
    done.sort(key=lambda r: r["i"])
    with open(args.out, "w") as f:
        for r in done:
            f.write(json.dumps(r) + "\n")
    behind = sorted(r["sent"] - r["due"] for r in done
                    if r["sent"] is not None and r["due"] is not None)
    summary = {"requests": len(done),
               "failed": sum(not r["ok"] for r in done),
               "late_p50_ms": round(1e3 * behind[len(behind) // 2], 3)
               if behind else None,
               "late_max_ms": round(1e3 * behind[-1], 3) if behind else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
