#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the main serving path once, through the entry point a user would
call, at the published widths of gpt2 (12 layers, d 768, 12 heads, vocab
50257, bf16, random weights from the registry's seed), and checks what
comes out by the repo's own means. Run from the root of a checkout:

    python3 chip_smoke.py

Phases, each a child process that is reaped before the next needs the
chip (a chip belongs to one process; this parent is stdlib-only and never
imports jax or tpu_engine — tests/test_kernels_tpu_compile.py holds it to
that):

  device   assert JAX's backend is a TPU; print versions and the device.
  kernels  python -m tpu_engine.ops.kernel_check: every Pallas kernel site
           compiled (interpret=False) at gpt2 and llama geometry and
           compared with its XLA reference.
  serve    python -m tpu_engine.serving.cli serve --model gpt2 --lanes 1
           --kv-block-size 16 --gen-prefill-chunk 256
           --warmup, then over HTTP through the gateway: greedy and seeded
           /generate (repeats identical), /generate/stream (equals the
           blocking result), a 640-token prompt (chunked prefill), eight
           concurrent streams (every slot live), /score, /infer twice
           (second cached), /health, /stats, /metrics; then the lane's
           counters, the idle pool and the server log are checked.
  blocks   one lane of `sdar-small-test` (a model that decodes by blocks
           of 4 tokens under a block-causal mask): a prompt with a tail,
           three blocks through the tick, twice the same tokens, both tick
           orders the same, every block counted and the pool idle after.
           The lane's read is the XLA gather here (`TPU_ENGINE_PAGED=0`:
           the test model's 32 lanes a token are below what Mosaic's DMA
           takes; the compiled block-mask kernel is in the kernel phase).
  planes   one lane of `ouro-small-test` (3 layers applied 3 times over one
           set of weights, a pool of 9 planes): a prompt across two chunks,
           twice the same tokens, the planes and the layer applications
           counted, the pool idle after. The XLA gather read, as `blocks`.
  tails    one lane of `lfm2-small-test` (a gated conv of three taps whose
           state row is ONE array, GQA, experts with no shared one): a
           prompt across three chunks and a prompt of one token, the tokens
           the one-shot forward's arg-max token for token (the conv over the
           token list against the conv over a padded sequence, on the
           chip), twice the same, every state row given back. The XLA
           gather read, as `blocks`; the compiled read at the cell's G = 4
           x 64 lanes and its grouped product are in the kernel phase
           (`kernel_check.CLASS_SHAPES`, `GROUPED_SHAPES`).
  groups   one lane of `granite_hybrid` cut to mamba, attention, mamba at d
           256 with the PUBLISHED recurrence shape (128 heads of (64, 128),
           B and C ONE group): `ssd_step` and `ssd_chunk` compiled at g = 1
           inside the tick, a prompt across three chunks beside a prompt of
           one token, every served token within 0.05 of the one-shot
           forward's largest logit (the XLA chunked form, no kernel), twice
           the same, every layer routed, every state row given back. The
           XLA gather read, as `blocks`.
  routes   one lane of `smallthinker-small-test` (experts chosen from the
           layer's input before its attention, ReGLU, three rotated window
           layers to one un-rotated full layer at G = 7, window 48): a
           prompt of 100 tokens across seven chunks, past the window,
           beside a prompt of one token, every served token within 0.05 of
           the one-shot forward's largest logit, twice the same, window
           blocks given back while the row ran and both pools idle after.
           The XLA gather read, as `blocks`; the compiled window read by
           class at the cell's 28 / 4 x 128, window 4096, and its banks'
           grouped product are in the kernel phase (`kernel_check.
           CELL_SHAPES`, `CLASS_SHAPES`, `GROUPED_SHAPES`).
  cache    the same launch again must reach ready without adding an entry
           to the compile cache.
  lanes    with >= 4 devices: --lanes 0 gives four lanes on four distinct
           devices and every lane passes the serve checks (printed skip
           otherwise).

Any failed check or child exit code ends the run non-zero. No TPU: exit
non-zero within seconds, never a CPU run. The last line of stdout on
success is {"ok": true, "device": {"platform", "kind", "count"}} as JAX
reports the device.
"""

import contextlib
import http.client
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
# Everything, compilation included, must end inside the 3400 s the chip
# call is given (measured, PR 49: device 18 s, kernels 990, serve 188,
# cache 32; PR 50: the kernel phase alone 896 s with the machine's compile
# cache warm and OVER 1400 s cold, its four new cases 125-160 s of it;
# PR 53: 2024 s cold; PR 54: 2258 s, of which the steps' live mixes 9 s
# and `olmo_hybrid`'s two kernels 96 s; PR 64: 1847 s alone with a fifth of
# the machine's cache, 95 cases, this PR's six 120 s of it, and PAST 2500 s
# in a whole smoke run cold behind a cell run: the kernel phase's limit is
# 2800 s and the whole run's 3340 since, inside the 3600 s a chip call may
# last).
DEADLINE = time.monotonic() + 3340

SERVE_FLAGS = ["--model", "gpt2", "--kv-block-size", "16",
               "--gen-prefill-chunk", "256", "--warmup"]
VOCAB = 50257            # gpt2's registry vocabulary
SLOTS = 8                # WorkerConfig.gen_max_batch_size
ATTENTION_PATHS = ("flash", "ragged", "quant_ragged")

_BLOCKS_CHILD = r"""
import json
import jax
from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                        create_model)
from tpu_engine.runtime.scheduler import ContinuousGenerator

assert jax.default_backend() == "tpu", jax.default_backend()
_ensure_builtin_models_imported()
spec = create_model("sdar-small-test")
params = spec.init(jax.random.PRNGKey(0))
prompt, out = [7, 11, 13, 17, 19, 23, 29, 31, 37], {}
for order in ("ahead", "drained"):
    gen = ContinuousGenerator(spec, params=params, n_slots=4,
                              dtype="float32", kv_block_size=16,
                              prefill_chunk=16, prefix_sharing=False)
    if order == "drained":
        gen._may_run_ahead = lambda: False
    try:
        first = gen.submit(prompt, max_new_tokens=11).result(300)
        again = gen.submit(prompt, max_new_tokens=11).result(300)
        stats = gen.stats()
    finally:
        gen.stop()
    assert first == again and len(first) == 11, (first, again)
    mixed, pool = stats["mixed"], stats["kv_pool"]
    # 11 tokens behind a tail of 1: blocks of 3 + 4 + 4, twice.
    assert mixed["blocks_finished"] == 6, mixed
    assert mixed["ticks"] == mixed["dispatches"], mixed
    assert pool["blocks_free"] == pool["blocks_total"], pool
    out[order] = first
assert out["ahead"] == out["drained"], out
print(json.dumps({"blocks": "ok", "tokens": out["ahead"]}))
"""

_PLANES_CHILD = r"""
import json
import jax
from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                        create_model)
from tpu_engine.runtime.scheduler import ContinuousGenerator

assert jax.default_backend() == "tpu", jax.default_backend()
_ensure_builtin_models_imported()
spec = create_model("ouro-small-test")
params = spec.init(jax.random.PRNGKey(0))
prompt = list(range(7, 30))                 # 23 tokens: chunks of 16 + 7
gen = ContinuousGenerator(spec, params=params, n_slots=4, dtype="float32",
                          kv_block_size=16, prefill_chunk=16,
                          prefix_sharing=False)
try:
    assert gen._pool.caches.k.shape[0] == 9, gen._pool.caches.k.shape
    first = gen.submit(prompt, max_new_tokens=9).result(300)
    again = gen.submit(prompt, max_new_tokens=9).result(300)
    stats = gen.stats()
finally:
    gen.stop()
assert first == again and len(first) == 9, (first, again)
mixed, pool = stats["mixed"], stats["kv_pool"]
assert (mixed["ut_steps"], mixed["kv_planes"]) == (3, 9), mixed
assert mixed["layer_passes"] == 9 * mixed["ticks"] > 0, mixed
assert mixed["ticks"] == mixed["dispatches"], mixed
assert pool["blocks_free"] == pool["blocks_total"], pool
print(json.dumps({"planes": "ok", "tokens": first,
                  "kv_planes": mixed["kv_planes"],
                  "layer_passes": mixed["layer_passes"]}))
"""

_TAILS_CHILD = r"""
import json
import jax
import jax.numpy as jnp
from tpu_engine.models.lfm2 import lfm2_apply
from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                        create_model)
from tpu_engine.runtime.scheduler import ContinuousGenerator

assert jax.default_backend() == "tpu", jax.default_backend()
_ensure_builtin_models_imported()
spec = create_model("lfm2-small-test")
params = spec.init(jax.random.PRNGKey(0))
prompts = [list(range(7, 47)), [5]]         # chunks of 16 + 16 + 8; one token
gen = ContinuousGenerator(spec, params=params, n_slots=4, dtype="float32",
                          kv_block_size=16, prefill_chunk=16,
                          prefix_sharing=False)
try:
    assert [x.shape for x in gen._spool.slab] == [(3, 5, 2, 48)]
    first = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    again = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    stats = gen.stats()
finally:
    gen.stop()
assert first == again and [len(t) for t in first] == [9, 9], (first, again)
with jax.default_matmul_precision("highest"):
    for prompt, tokens in zip(prompts, first):
        seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        logits = lfm2_apply(params, seq, spec.config, dtype=jnp.float32)[0]
        want = logits[len(prompt) - 1:].argmax(-1).tolist()
        assert tokens == want, (tokens, want)
mixed, state, pool = stats["mixed"], stats["state_pool"], stats["kv_pool"]
assert mixed["ticks"] == mixed["dispatches"] > 0, mixed
assert state["rows_held"] == 0 and state["bytes_per_row"] == 1152, state
assert pool["blocks_free"] == pool["blocks_total"], pool
print(json.dumps({"tails": "ok", "tokens": first[0],
                  "distinct": len(set(first[0] + first[1]))}))
"""

_GROUPS_CHILD = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from tpu_engine.models.granite_hybrid import granite_hybrid_apply
from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                        create_model)
from tpu_engine.runtime.scheduler import ContinuousGenerator

assert jax.default_backend() == "tpu", jax.default_backend()
_ensure_builtin_models_imported()
spec = create_model(
    "granite_hybrid", n_layers=3,
    layer_types=("mamba", "attention", "mamba"), d_model=256, n_heads=2,
    n_kv_heads=1, d_ff_expert=128, d_ff_shared=256, n_experts=8, top_k=2,
    held_count=4, vocab=1024, max_seq=256, param_dtype="float32")
cfg = spec.config
assert (cfg.lin_heads, cfg.ssm_head_dim, cfg.d_state, cfg.n_groups) == (
    128, 64, 128, 1), cfg
params = jax.jit(spec.init)(jax.random.PRNGKey(0))
prompts = [list(range(7, 47)), [5]]         # chunks of 16 + 16 + 8; one token
gen = ContinuousGenerator(spec, params=params, n_slots=4, dtype="float32",
                          kv_block_size=16, prefill_chunk=16,
                          prefix_sharing=False)
try:
    assert [x.shape for x in gen._spool.slab] == [(2, 5, 128, 64, 128),
                                                  (2, 5, 8, 3168)]
    first = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    again = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    stats = gen.stats()
finally:
    gen.stop()
assert first == again and [len(t) for t in first] == [9, 9], (first, again)
worst = 0.0
with jax.default_matmul_precision("highest"):
    for prompt, tokens in zip(prompts, first):
        seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        logits = np.asarray(granite_hybrid_apply(
            params, seq, cfg, dtype=jnp.float32)[0])[len(prompt) - 1:]
        gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
        worst = max(worst, float((gap / logits.std(-1)).max()))
assert worst < 0.05, worst
mixed, state, pool, moe = (stats["mixed"], stats["state_pool"],
                           stats["kv_pool"], stats["moe"])
assert mixed["ticks"] == mixed["dispatches"] > 0, mixed
assert state["rows_held"] == 0, state
assert state["bytes_per_row"] == 2 * (128 * 64 * 128 + 3 * 8448) * 4, state
assert pool["blocks_free"] == pool["blocks_total"], pool
assert np.asarray(moe["rows_by_expert"]).shape == (3, 8), moe
print(json.dumps({"groups": "ok", "tokens": first[0],
                  "worst_gap_in_logit_std": worst,
                  "distinct": len(set(first[0] + first[1]))}))
"""

_ROUTES_CHILD = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from tpu_engine.models.registry import (_ensure_builtin_models_imported,
                                        create_model)
from tpu_engine.models.smallthinker import smallthinker_apply
from tpu_engine.runtime.scheduler import ContinuousGenerator
assert jax.default_backend() == "tpu", jax.default_backend()
_ensure_builtin_models_imported()
spec = create_model("smallthinker-small-test")
cfg = spec.config
params = jax.jit(spec.init)(jax.random.PRNGKey(0))
prompts = [list(range(7, 107)), [5]]        # past the window of 48; one token
gen = ContinuousGenerator(spec, params=params, n_slots=4, dtype="float32",
                          kv_block_size=16, prefill_chunk=16,
                          prefix_sharing=False)
try:
    first = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    again = [f.result(300) for f in
             [gen.submit(p, max_new_tokens=9) for p in prompts]]
    stats = gen.stats()
finally:
    gen.stop()
assert first == again and [len(t) for t in first] == [9, 9], (first, again)
worst = 0.0
with jax.default_matmul_precision("highest"):
    for prompt, tokens in zip(prompts, first):
        seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        logits = np.asarray(smallthinker_apply(
            params, seq, cfg, dtype=jnp.float32)[0])[len(prompt) - 1:]
        gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
        worst = max(worst, float((gap / logits.std(-1)).max()))
assert worst < 0.05, worst
mixed, pool, moe = stats["mixed"], stats["kv_pool"], stats["moe"]
assert mixed["ticks"] == mixed["dispatches"] > 0, mixed
assert pool["blocks_free"] == pool["blocks_total"], pool
assert pool["window_blocks_held"] == pool["full_blocks_held"] == 0, pool
assert pool["window_blocks_freed"] > 0, pool
assert np.asarray(moe["rows_by_expert"]).shape == (8, 8), moe
assert moe["assignments"] == moe["assignments_held"], moe
print(json.dumps({"routes": "ok", "tokens": first[0],
                  "worst_gap_in_logit_std": worst,
                  "window_blocks_freed": pool["window_blocks_freed"],
                  "distinct": len(set(first[0] + first[1]))}))
"""
_DEVICE_CHILD = r"""
import importlib.metadata as md, json, sys
import jax, jaxlib
backend = jax.default_backend()
if backend != "tpu":
    sys.exit(f"no TPU found: JAX's default backend is {backend!r}")
devices = jax.devices()
print(json.dumps({"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": md.version("libtpu"),
                  "device": {"platform": devices[0].platform,
                             "kind": devices[0].device_kind,
                             "count": len(devices)}}))
"""


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def say(**fields):
    print(json.dumps(fields), flush=True)


def time_left(cap):
    left = DEADLINE - time.monotonic()
    check(left > 0, "out of time: the run must end inside 3400 s")
    return min(cap, left)


def child_env():
    env = dict(os.environ)
    # Never a CPU run, and no hand-picked kernel: the children decide the
    # platform and the attention paths the way a user's launch would.
    for name in ("TPU_ENGINE_PLATFORM", "TPU_ENGINE_PAGED",
                 "TPU_ENGINE_FLASH"):
        env.pop(name, None)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"   # the log is read while the child runs
    return env


def cache_dir():
    """The compile cache the children use (utils.checkpoint's rule)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def cache_entries():
    try:
        return {n for n in os.listdir(cache_dir()) if n.endswith("-cache")}
    except FileNotFoundError:
        return set()


def run_child(name, argv, cap, env=None):
    """Run one child to its end; returns its stdout. Its stderr goes to a
    log under chiprun_out/. `env`: what the child's environment adds."""
    log_path = os.path.join(OUT_DIR, f"{name}.err.log")
    with open(log_path, "w") as err:
        proc = subprocess.Popen(argv, cwd=HERE,
                                env=dict(child_env(), **(env or {})),
                                stdout=subprocess.PIPE, stderr=err, text=True)
        try:
            out, _ = proc.communicate(timeout=time_left(cap))
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise SmokeFailure(f"{name}: child still running after its "
                               f"time limit")
    sys.stdout.write(out)
    sys.stdout.flush()
    if proc.returncode != 0:
        with open(log_path) as f:
            tail = f.read()[-2000:]
        raise SmokeFailure(f"{name}: child exited {proc.returncode}\n{tail}")
    return out


# -- HTTP ---------------------------------------------------------------------

def request(port, method, path, payload=None, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = None if payload is None else json.dumps(payload)
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def call(port, method, path, payload=None):
    status, data = request(port, method, path, payload)
    check(status == 200, f"{method} {path} -> {status}: {data[:300]!r}")
    return data


def call_json(port, method, path, payload=None):
    return json.loads(call(port, method, path, payload))


def generate(port, rid, **fields):
    out = call_json(port, "POST", "/generate",
                    dict(fields, request_id=rid))
    return out["tokens"], out["node_id"]


def stream(port, rid, **fields):
    data = call(port, "POST", "/generate/stream",
                dict(fields, request_id=rid))
    events = [json.loads(block[len("data: "):])
              for block in data.decode().split("\n\n")
              if block.startswith("data: ")]
    check(events and events[-1].get("done") is True
          and "error" not in events[-1], f"stream {rid}: {events[-1:]}")
    streamed = [t for e in events[:-1] for t in e["tokens"]]
    check(streamed == events[-1]["tokens"],
          f"stream {rid}: deltas != final tokens")
    return streamed


def tokens(rng, n):
    return [rng.randrange(VOCAB) for _ in range(n)]


# -- the serving child --------------------------------------------------------

class Server:
    """One `cli serve` child. `with Server(...)` always ends with the child
    signalled, waited for and — if it ignores SIGTERM — killed."""

    def __init__(self, name, lanes):
        self.name = name
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        self.port = sock.getsockname()[1]
        sock.close()
        self.log_path = os.path.join(OUT_DIR, f"{name}.log")
        self.argv = [sys.executable, "-m", "tpu_engine.serving.cli", "serve",
                     *SERVE_FLAGS, "--lanes", str(lanes),
                     "--port", str(self.port)]
        self.proc = None
        self.ready_s = None

    def __enter__(self):
        self._log = open(self.log_path, "w")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(self.argv, cwd=HERE, env=child_env(),
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)
        limit = time.monotonic() + time_left(900)
        while True:
            check(self.proc.poll() is None,
                  f"{self.name}: server exited {self.proc.returncode} "
                  f"before ready\n{self.log()[-3000:]}")
            check(time.monotonic() < limit,
                  f"{self.name}: server not ready in time\n"
                  f"{self.log()[-3000:]}")
            try:
                if request(self.port, "GET", "/stats", timeout=5)[0] == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        self.ready_s = round(time.monotonic() - t0, 1)
        return self

    def __exit__(self, exc_type, exc, tb):
        clean = self.stop()
        self._log.close()
        if exc_type is None:
            check(clean, f"{self.name}: server did not exit 0 on SIGTERM "
                         f"(rc {self.proc.returncode})")

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return False
        return self.proc.returncode == 0

    def log(self):
        with open(self.log_path) as f:   # the child writes the fd itself
            return f.read()


def check_banner(server, n_lanes):
    """The start-up banner: backend tpu, every attention path the Pallas
    kernel with interpret off, each lane on its own device."""
    log = server.log()
    lines = [ln.strip() for ln in log.splitlines()]
    check(any(ln.startswith("backend: tpu,") for ln in lines),
          f"{server.name}: banner does not say backend tpu")
    for path in ATTENTION_PATHS:
        check(f"attention {path}: pallas interpret=False" in lines,
              f"{server.name}: attention path {path!r} is not the compiled "
              f"Pallas kernel")
    lane_devices = [ln.split("-> device ")[1] for ln in lines
                    if ln.startswith("lane ") and "-> device " in ln]
    check(len(lane_devices) == n_lanes
          and len(set(lane_devices)) == n_lanes,
          f"{server.name}: lanes -> devices {lane_devices}, wanted "
          f"{n_lanes} distinct")
    def value(key):
        return next(ln[len(key):] for ln in lines if ln.startswith(key))

    front = value("front: ")
    say(phase=server.name, lane_devices=lane_devices, front=front,
        native_core="loaded" if "native" in front else "python front",
        compile_cache=value("compile cache: "))
    return lane_devices


def drive(server, n_lanes):
    """The requests of the smoke, then the per-lane assertions."""
    port = server.port
    rng = random.Random(0)

    # Spread request ids until every lane's generator has served.
    lanes_seen = set()
    for i in range(64 * n_lanes):
        if len(lanes_seen) == n_lanes:
            break
        toks, node = generate(port, f"spread-{i}",
                              prompt_tokens=tokens(rng, 9), max_new_tokens=4)
        check(len(toks) == 4, f"spread-{i}: {len(toks)} tokens")
        lanes_seen.add(node)
    check(len(lanes_seen) == n_lanes,
          f"only lanes {sorted(lanes_seen)} served after {i} request ids")

    # Greedy and seeded: a repeat in the same process is identical. The
    # prompt is shorter than one KV block, so the repeat recomputes the
    # same dispatch rather than reusing radix blocks.
    prompt = tokens(rng, 12)
    greedy, _ = generate(port, "greedy-1", prompt_tokens=prompt,
                         max_new_tokens=16)
    again, _ = generate(port, "greedy-2", prompt_tokens=prompt,
                        max_new_tokens=16)
    check(len(greedy) == 16 and greedy == again,
          f"greedy repeat differs: {greedy} vs {again}")
    sampled = dict(prompt_tokens=prompt, max_new_tokens=16,
                   temperature=0.8, seed=1234)
    seeded, _ = generate(port, "seeded-1", **sampled)
    again, _ = generate(port, "seeded-2", **sampled)
    check(len(seeded) == 16 and seeded == again,
          f"seeded repeat differs: {seeded} vs {again}")
    check(all(0 <= t < VOCAB for t in greedy + seeded), "token out of range")

    # Streaming equals blocking.
    check(stream(port, "stream-1", prompt_tokens=prompt,
                 max_new_tokens=16) == greedy,
          "streamed tokens != blocking tokens")

    # One prompt longer than the prefill chunk: chunked prefill through the
    # wide ragged window. Its repeat rides the radix prefix blocks — a
    # different dispatch shape, so identity is reported, not demanded.
    long_prompt = tokens(rng, 640)
    first, _ = generate(port, "long-1", prompt_tokens=long_prompt,
                        max_new_tokens=8)
    second, _ = generate(port, "long-2", prompt_tokens=long_prompt,
                         max_new_tokens=8)
    check(len(first) == 8 and len(second) == 8, "long prompt token count")

    # Eight concurrent streams per lane: every slot live at once.
    n_streams = SLOTS * n_lanes
    results, errors = {}, []

    def one_stream(i):
        try:
            results[i] = stream(port, f"burst-{i}",
                                prompt_tokens=tokens(random.Random(i), 24),
                                max_new_tokens=192)
        except Exception as exc:   # re-raised below, in the main thread
            errors.append(exc)

    threads = [threading.Thread(target=one_stream, args=(i,))
               for i in range(n_streams)]
    for t in threads:
        t.start()
    peak_active = 0
    while any(t.is_alive() for t in threads):
        mixed = call_json(port, "GET", "/stats").get("mixed", {})
        peak_active = max(peak_active, sum(m.get("active", 0)
                                           for m in mixed.values()))
        time.sleep(0.02)
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    check(len(results) == n_streams
          and all(len(r) == 192 for r in results.values()),
          "a concurrent stream came back short")
    if n_lanes == 1:
        check(peak_active == SLOTS,
              f"peak live rows {peak_active}, wanted all {SLOTS} slots")

    # /score and /infer (the unified one-shot rows beside decode rows).
    score = call_json(port, "POST", "/score", {
        "request_id": "score-1", "prompt_tokens": tokens(rng, 16),
        "completion_tokens": tokens(rng, 8)})
    check(len(score["logprobs"]) == 8
          and all(math.isfinite(x) and x <= 0 for x in score["logprobs"])
          and math.isclose(score["total_logprob"], sum(score["logprobs"]),
                           rel_tol=1e-3),
          f"/score: {score}")
    infer = {"request_id": "infer-1",
             "input_data": [float(t) for t in tokens(rng, 128)]}
    cold = call_json(port, "POST", "/infer", infer)
    warm = call_json(port, "POST", "/infer", dict(infer,
                                                  request_id="infer-2"))
    check(cold["cached"] is False and warm["cached"] is True,
          "/infer: second identical request was not served from the cache")
    check(len(cold["output_data"]) == VOCAB
          and all(math.isfinite(x) for x in cold["output_data"])
          and warm["output_data"] == cold["output_data"],
          "/infer: output is not a finite vocabulary-wide vector")

    stats = call_json(port, "GET", "/stats")
    check("kv_pool" in stats and "mixed" in stats,
          "/stats lacks kv_pool/mixed")
    metrics = call(port, "GET", "/metrics").decode()
    check("tpu_engine_mixed_" in metrics
          and "tpu_engine_ttft_seconds" in metrics, "/metrics lacks series")
    health = call_json(port, "GET", "/health")
    check(health["healthy"] is True, f"/health: {health.get('healthy')}")

    # Per lane, once idle: no scheduler recovery happened, every mixed tick
    # was exactly one dispatch, and the pool got every block back.
    for node in sorted(lanes_seen):
        limit = time.monotonic() + 30
        while True:
            gen = call_json(port, "GET", f"/health/{node}")["generator"]
            if gen["active"] == 0 or time.monotonic() > limit:
                break
            time.sleep(0.2)
        pool, mixed = gen["kv_pool"], gen["mixed"]
        check(gen["active"] == 0, f"{node}: rows still live when idle")
        check(gen.get("failures", 0) == 0,
              f"{node}: generator.failures = {gen.get('failures')}")
        check(mixed["ticks"] == mixed["dispatches"] > 0,
              f"{node}: mixed ticks {mixed['ticks']} != dispatches "
              f"{mixed['dispatches']}")
        check(pool["blocks_free"] + pool["radix_nodes"]
              >= pool["blocks_total"], f"{node}: leaked KV blocks: {pool}")
        say(phase=server.name, lane=node, ticks=mixed["ticks"],
            dispatches=mixed["dispatches"], completed=gen["completed"],
            blocks_free=pool["blocks_free"],
            radix_nodes=pool["radix_nodes"],
            blocks_total=pool["blocks_total"])
    say(phase=server.name, requests="ok", peak_live_rows=peak_active,
        prefix_reuse_repeat_identical=first == second)


def check_log(server):
    log = server.log()
    for word in ("skipped", "Traceback"):
        check(word not in log,
              f"{server.name}: server log contains {word!r}\n{log[-3000:]}")


def serve_phase(name, lanes, n_lanes):
    with Server(name, lanes) as server:
        lane_devices = check_banner(server, n_lanes)
        t0 = time.monotonic()
        drive(server, n_lanes)
        drive_s = round(time.monotonic() - t0, 1)
    check_log(server)
    return server.ready_s, drive_s, lane_devices


def main():
    if not os.path.isdir(os.path.join(HERE, "tpu_engine")):
        sys.exit("chip_smoke: run from the root of a tpu-inference-engine "
                 "checkout (no tpu_engine/ beside this script)")
    os.makedirs(OUT_DIR, exist_ok=True)
    times = {}

    @contextlib.contextmanager
    def phase(name):
        t0 = time.monotonic()
        yield
        times[name] = round(time.monotonic() - t0, 1)

    with phase("device"):
        out = run_child("device", [sys.executable, "-c", _DEVICE_CHILD], 120)
        info = json.loads(out.strip().splitlines()[-1])
        device = info["device"]
        check(device["platform"] == "tpu", f"device is {device}")

    with phase("kernels"):
        # ~900 s on a v5e with a warm compile cache and over 1400 s cold,
        # with the benchmark cells' own shapes and the walk's cases (PRs
        # 46, 48, 50: the gather references of the cell and class cases
        # are most of it).
        run_child("kernels",
                  [sys.executable, "-m", "tpu_engine.ops.kernel_check"], 2800)

    with phase("serve"):
        cold_ready, drive_s, _ = serve_phase("serve", 1, 1)
    say(phase="serve", time_to_ready_s=cold_ready, requests_s=drive_s)

    # The same launch again: everything start-up compiles is in the cache.
    with phase("cache"):
        before = cache_entries()
        check(before, f"no compile-cache entries under {cache_dir()}")
        with Server("cache", 1) as server:
            added = cache_entries() - before
            check(not added, f"relaunch added {len(added)} compile-cache "
                             f"entries: {sorted(added)[:8]}")
            check_banner(server, 1)
        check_log(server)
    say(phase="cache", cache_dir=cache_dir(), entries=len(before),
        added_by_relaunch=0, cold_time_to_ready_s=cold_ready,
        cached_time_to_ready_s=server.ready_s)

    with phase("blocks"):
        out = run_child("blocks", [sys.executable, "-c", _BLOCKS_CHILD], 300,
                        env={"TPU_ENGINE_PAGED": "0"})
        check(json.loads(out.strip().splitlines()[-1])["blocks"] == "ok",
              "the block-decoding lane's smoke did not end ok")
    say(phase="blocks", seconds=times["blocks"])

    with phase("planes"):
        out = run_child("planes", [sys.executable, "-c", _PLANES_CHILD], 300,
                        env={"TPU_ENGINE_PAGED": "0"})
        check(json.loads(out.strip().splitlines()[-1])["planes"] == "ok",
              "the looped lane's smoke did not end ok")
    say(phase="planes", seconds=times["planes"])

    with phase("tails"):
        out = run_child("tails", [sys.executable, "-c", _TAILS_CHILD], 300,
                        env={"TPU_ENGINE_PAGED": "0"})
        check(json.loads(out.strip().splitlines()[-1])["tails"] == "ok",
              "the conv-tail lane's smoke did not end ok")
    say(phase="tails", seconds=times["tails"])

    with phase("groups"):
        out = run_child("groups", [sys.executable, "-c", _GROUPS_CHILD], 300,
                        env={"TPU_ENGINE_PAGED": "0"})
        check(json.loads(out.strip().splitlines()[-1])["groups"] == "ok",
              "the one-group recurrence lane's smoke did not end ok")
    say(phase="groups", seconds=times["groups"])

    with phase("routes"):
        out = run_child("routes", [sys.executable, "-c", _ROUTES_CHILD], 300,
                        env={"TPU_ENGINE_PAGED": "0"})
        check(json.loads(out.strip().splitlines()[-1])["routes"] == "ok",
              "the early-route lane's smoke did not end ok")
    say(phase="routes", seconds=times["routes"])

    if device["count"] >= 4:
        with phase("lanes"):
            ready_s, drive_s, lane_devices = serve_phase("lanes", 0,
                                                         device["count"])
        say(phase="lanes", time_to_ready_s=ready_s, requests_s=drive_s,
            lane_devices=lane_devices)
    else:
        say(phase="lanes", skipped=f"{device['count']} device(s) visible; "
                                   f"the four-lane phase needs 4")

    say(phase_seconds=times, versions={k: info[k] for k in
                                       ("jax", "jaxlib", "libtpu")})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"chip_smoke FAILED: {failure}", file=sys.stderr, flush=True)
        sys.exit(1)
