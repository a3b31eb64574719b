"""engine-lint tier-1 gate + per-analyzer unit fixtures.

Two layers:

- the REPO test: the full suite over ``tpu_engine/`` in-process must
  report zero non-baseline findings in under 20 s — any unguarded
  access to registered state, hot-path trace leak, unpaired decision
  counter, or CLI/config default drift fails tier-1 at the lint layer
  instead of (or before) the chaos harnesses;
- FIXTURE tests: each analyzer is fed small known-violating and
  known-clean snippets against a synthetic registry, so a regression in
  a rule is caught independently of the codebase it scans.

Plus targeted regression tests for the two real findings the first
engine-lint run surfaced in ``serving/gateway.py`` (membership dicts
read outside the gateway lock in ``_route_inner``/``_try_node``).
"""

import ast
import json
import os
import re
import time

import pytest

from tools.analyze import baseline as baseline_mod
from tools.analyze import counters as counters_mod
from tools.analyze import flags as flags_mod
from tools.analyze import hotpath as hotpath_mod
from tools.analyze import locks as locks_mod
from tools.analyze.core import (
    REPO_ROOT,
    apply_waivers,
    build_index,
    run_suite,
)
from tools.analyze.registry import (
    ENGINE_REGISTRY,
    GuardedEntry,
    Registry,
    ThreadOwnedEntry,
)

pytestmark = pytest.mark.lint


def _fix_registry(**over):
    base = dict(
        package="fix",
        lock_aliases=((None, "self.lock", "Pool.lock"),
                      (None, "pool.lock", "Pool.lock")),
        reentrant=frozenset(),
        guarded=(GuardedEntry(attrs=("_free",), lock="Pool.lock",
                              classes=("Pool",), receivers=("pool",)),),
        thread_owned=(),
        caller_locked=frozenset({"Pool.*"}),
        receiver_aliases={"pool": "Pool"},
        counter_receivers=frozenset({"resilience"}),
        span_tracer_attrs=frozenset({"tracer"}),
        span_sink_attrs=frozenset({"sink"}),
        hot_static_params=frozenset({"cfg"}),
        tick_entries=("m:Sched._tick",),
        cli_module="cli",
        config_module="config",
        config_classes=("Cfg",),
    )
    base.update(over)
    return Registry(**base)


def _index(reg, **sources):
    return build_index({name: (f"{name}.py", src)
                        for name, src in sources.items()},
                       reg.receiver_aliases)


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- the tier-1 gate ----------------------------------------------------------

def test_repo_lint_clean_and_fast():
    t0 = time.perf_counter()
    report = run_suite(REPO_ROOT, ENGINE_REGISTRY)
    elapsed = time.perf_counter() - t0
    new, _old = baseline_mod.split(report.findings)
    assert not new, "engine-lint regressions:\n" + "\n".join(
        f.format() for f in new)
    assert elapsed < 20, f"engine-lint took {elapsed:.1f}s (budget 20s)"
    # The waiver mechanism is exercised by real code (breaker stats
    # reads, scheduler GIL-safe scrapes) — if these vanish the waiver
    # path is untested, so pin that some exist.
    assert report.waived, "expected inline lockfree-ok waivers in-tree"


def test_baseline_file_sorted_and_deduped():
    with open(baseline_mod.DEFAULT_PATH, encoding="utf-8") as f:
        data = json.load(f)
    keys = data["findings"]
    assert keys == sorted(set(keys))


def _bench_scenarios():
    """The `choices` of bench.py's `--scenario`, read from its source."""
    with open(os.path.join(REPO_ROOT, "bench.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    (call,) = [n for n in ast.walk(tree)
               if isinstance(n, ast.Call) and n.args
               and isinstance(n.args[0], ast.Constant)
               and n.args[0].value == "--scenario"]
    return set(ast.literal_eval(
        next(k.value for k in call.keywords if k.arg == "choices")))


_CITING = ("tpu_engine", "tools", "diagnostics.py", "README.md", "DESIGN.md",
           "MIGRATION.md", ".claude/skills/verify/SKILL.md")


def test_nothing_cites_a_deleted_bench_arm_or_a_pre_chip_record():
    """A speed of this system is a PERF_LEDGER.jsonl line from
    benchmarks/run.py on the chip; a guarantee is a tier-1 test. So no
    source file, help string or document sends a reader to a
    `bench.py --scenario` that bench.py does not accept (only bench.py
    has that flag), or to a `BENCH_r*` / `MULTICHIP_r*` file: both were
    CPU runs of a toy model (deleted in PR 31)."""
    accepted = _bench_scenarios()
    assert accepted == {"infer", "mixed", "miss-sweep"}
    # A mention may be broken over lines of a help string or a comment.
    scenario = re.compile(r"--scenario(?:[\s\"'`#=]|\\n)+([a-z][a-z0-9-]*)")
    record = re.compile(r"(?:BENCH|MULTICHIP)_r[0-9*]")
    paths = []
    for root in _CITING:
        full = os.path.join(REPO_ROOT, root)
        if os.path.isfile(full):
            paths.append(full)
        for d, _dirs, files in os.walk(full):
            paths += [os.path.join(d, f) for f in files
                      if f.endswith((".py", ".md", ".sh", ".json"))]
    assert len(paths) > 50
    bad = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        rel = os.path.relpath(path, REPO_ROOT)
        bad += [f"{rel}: --scenario {m.group(1)}"
                for m in scenario.finditer(text)
                if m.group(1) not in accepted]
        bad += [f"{rel}: {m.group(0)}" for m in record.finditer(text)]
    assert not bad, "\n".join(bad)


_MODELS = os.path.join(REPO_ROOT, "tpu_engine", "models")
# What builds a `ModelSpec` beside `registry.causal_lm_spec`: the models
# that are no language model, and `ssd`, whose one-shot `apply` is a scan
# over a state that yields one row of logits, not a full forward.
_OWN_SPEC = {"registry.py", "bert.py", "mlp.py", "resnet.py", "yolo.py",
             "onnx_graph.py", "ssd.py"}


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(_MODELS)
    if f.endswith(".py") and f != "tick_tokens.py"))
def test_a_model_file_lists_no_tokens_and_wraps_no_wire_contract(name):
    """How a tick's tokens are listed is `models/tick_tokens.py`'s to say,
    and a causal LM's one-shot wire wrapper `registry.causal_lm_spec`'s
    (PR 62): a family file that calls the plans under the list itself, or
    builds its own `ModelSpec`, has begun a copy."""
    with open(os.path.join(_MODELS, name), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    called = {getattr(n.func, "attr", getattr(n.func, "id", None))
              for n in ast.walk(tree) if isinstance(n, ast.Call)}
    assert not called & {"tile_plan", "tile_slots", "tiles_bound",
                         "class_plan"}, name
    assert ("ModelSpec" in called) <= (name in _OWN_SPEC), name


# -- lock discipline ----------------------------------------------------------

_LOCK_VIOLATING = '''
import threading

class Pool:
    def __init__(self):
        self.lock = threading.Lock()
        self._free = []

    def alloc(self):
        return self._free.pop()

class User:
    def __init__(self, pool):
        self.p = pool

    def bad_attr(self, pool):
        return pool._free[0]

    def bad_call(self, pool):
        return pool.alloc()

    def good(self, pool):
        with pool.lock:
            return pool.alloc()
'''

_LOCK_CLEAN = '''
import threading

class Pool:
    def __init__(self):
        self.lock = threading.Lock()
        self._free = []

    def alloc(self):
        return self._free.pop()

class User:
    def use(self, pool):
        with pool.lock:
            pool._free.append(1)
            return pool.alloc()
'''


def test_lock_analyzer_flags_unguarded_access_and_call():
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=_LOCK_VIOLATING), reg)
    unguarded = [f for f in findings if f.rule == "lock-unguarded"]
    assert {f.func for f in unguarded} == {"m:User.bad_attr",
                                           "m:User.bad_call"}
    # caller-locked Pool.alloc itself is never the finding — its
    # unguarded CALLERS are.
    assert not any(f.func.startswith("m:Pool.") for f in findings)


def test_lock_analyzer_clean_fixture():
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=_LOCK_CLEAN), reg)
    assert [f for f in findings if f.rule == "lock-unguarded"] == []


def test_lock_analyzer_waiver():
    reg = _fix_registry()
    src = _LOCK_VIOLATING.replace(
        "return pool._free[0]",
        "return pool._free[0]  # lint: lockfree-ok fixture")
    idx = _index(reg, m=src)
    report = apply_waivers(locks_mod.analyze(idx, reg), idx)
    assert "m:User.bad_attr" not in {f.func for f in report.findings}
    assert "m:User.bad_attr" in {f.func for f in report.waived}


_ORDER_CYCLE = '''
import threading

class A:
    def __init__(self):
        self.x_lock = threading.Lock()
        self.y_lock = threading.Lock()

    def one(self):
        with self.x_lock:
            with self.y_lock:
                pass

    def two(self):
        with self.y_lock:
            with self.x_lock:
                pass
'''


def test_lock_order_cycle_detected():
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=_ORDER_CYCLE), reg)
    assert "lock-order" in _rules(findings)
    # One consistent order: no cycle.
    clean = _ORDER_CYCLE.replace(
        "with self.y_lock:\n            with self.x_lock:",
        "with self.x_lock:\n            with self.y_lock:")
    findings = locks_mod.analyze(_index(reg, m=clean), reg)
    assert "lock-order" not in _rules(findings)


def test_nested_def_under_with_is_not_held():
    """A function DEFINED inside a `with lock:` body runs later,
    lock-free — it must not inherit the held set (false lock-reentry)
    nor contribute order edges (false lock-order cycles)."""
    src = '''
import threading

class A:
    def __init__(self):
        self.x_lock = threading.Lock()
        self.y_lock = threading.Lock()
        self._cbs = []

    def flush(self):
        with self.x_lock:
            def cb():
                with self.x_lock:
                    pass
            self._cbs.append(cb)

    def other(self):
        with self.x_lock:
            def later():
                with self.y_lock:
                    pass
            self._cbs.append(later)

    def legit(self):
        with self.y_lock:
            with self.x_lock:
                pass
'''
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=src), reg)
    # cb's re-take of x_lock is deferred: no reentry. later's y_lock is
    # deferred: no x->y edge, so legit's y->x nesting is no cycle.
    assert "lock-reentry" not in _rules(findings)
    assert "lock-order" not in _rules(findings)


def test_lock_order_three_lock_cycle_anchored():
    """A 3-lock inversion must report a REAL cycle path (every
    consecutive pair is an actual nesting) anchored to a witnessed edge
    — not an unanchored '?' from the sorted SCC."""
    src = '''
import threading

class A:
    def __init__(self):
        self.x_lock = threading.Lock()
        self.y_lock = threading.Lock()
        self.z_lock = threading.Lock()

    def one(self):
        with self.z_lock:
            with self.y_lock:
                pass

    def two(self):
        with self.y_lock:
            with self.x_lock:
                pass

    def three(self):
        with self.x_lock:
            with self.z_lock:
                pass
'''
    reg = _fix_registry()
    findings = [f for f in locks_mod.analyze(_index(reg, m=src), reg)
                if f.rule == "lock-order"]
    assert len(findings) == 1
    f = findings[0]
    assert f.file == "m.py" and f.line > 0 and f.func.startswith("m:A.")
    path = f.message.split("cycle: ", 1)[1].split(" -> ")
    assert path[0] == path[-1] and len(path) == 4
    real_edges = {("A.z_lock", "A.y_lock"), ("A.y_lock", "A.x_lock"),
                  ("A.x_lock", "A.z_lock")}
    assert all((a, b) in real_edges for a, b in zip(path, path[1:]))


def test_lock_order_interprocedural_edge():
    """A callee's acquisition counts as nested under the caller's held
    lock — the shape `with pool.lock: self._exe()` (which acquires the
    compile lock) must produce the pool->compile edge, and a reverse
    nesting elsewhere must then be a cycle."""
    src = '''
import threading

class A:
    def __init__(self):
        self.x_lock = threading.Lock()
        self.y_lock = threading.Lock()

    def helper(self):
        with self.y_lock:
            pass

    def one(self):
        with self.x_lock:
            self.helper()

    def two(self):
        with self.y_lock:
            with self.x_lock:
                pass
'''
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=src), reg)
    assert "lock-order" in _rules(findings)


def test_lock_reentry_detected():
    src = '''
import threading

class A:
    def __init__(self):
        self.x_lock = threading.Lock()

    def boom(self):
        with self.x_lock:
            with self.x_lock:
                pass
'''
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=src), reg)
    assert "lock-reentry" in _rules(findings)
    # Registered-reentrant locks (RLock) may nest.
    reg2 = _fix_registry(reentrant=frozenset({"A.x_lock"}))
    findings = locks_mod.analyze(_index(reg2, m=src), reg2)
    assert "lock-reentry" not in _rules(findings)


_THREAD_OWNED = '''
class Sched:
    def __init__(self):
        self._rows = []

    def _loop(self):
        self._helper()

    def _helper(self):
        self._rows.append(1)

    def stats(self):
        return len(self._rows)
'''


def test_thread_owned_analyzer():
    reg = _fix_registry(thread_owned=(ThreadOwnedEntry(
        attrs=("_rows",), owner_class="Sched", module="m",
        entries=("Sched._loop",), thread="loop"),))
    findings = locks_mod.analyze(_index(reg, m=_THREAD_OWNED), reg)
    owned = [f for f in findings if f.rule == "thread-owned"]
    assert {f.func for f in owned} == {"m:Sched.stats"}  # _helper is
    # reachable from the loop entry, stats is not.


# -- hot path -----------------------------------------------------------------

_HOT_VIOLATING = '''
import jax
import numpy as np

def build():
    def step(x, n):
        if x > 0:
            x = x + 1
        y = np.asarray(x)
        z = x.item()
        return x
    return jax.jit(step)
'''

_HOT_CLEAN = '''
import jax
import jax.numpy as jnp

def build(flag):
    def step(x, cfg):
        if flag:
            x = x + 1
        if cfg.causal:
            x = x * 2
        if x.shape[0] > 2:
            x = x[:2]
        w = len(x)
        if w > 4:
            x = x * 1
        y = jnp.asarray(x)
        return y
    return jax.jit(step)
'''


def test_hotpath_flags_sync_and_branch():
    reg = _fix_registry()
    findings = hotpath_mod.analyze(_index(reg, m=_HOT_VIOLATING), reg)
    rules = [f.rule for f in findings]
    assert rules.count("hot-branch") == 1
    assert rules.count("hot-sync") == 2  # np.asarray + .item()


def test_hotpath_clean_fixture():
    """Closure flags, static config, shape math, and jnp stay silent."""
    reg = _fix_registry()
    findings = hotpath_mod.analyze(_index(reg, m=_HOT_CLEAN), reg)
    assert findings == []


def test_hotpath_transitive_callee_scanned():
    src = '''
import jax

def helper(x):
    return x.item()

def build():
    def step(x):
        return helper(x)
    return jax.jit(step)
'''
    reg = _fix_registry()
    findings = hotpath_mod.analyze(_index(reg, m=src), reg)
    assert any(f.rule == "hot-sync" and f.func == "m:helper"
               for f in findings)


def test_hotpath_per_tick_jit():
    src = '''
import jax

class Sched:
    def _tick(self, x):
        def f(v):
            return v
        return jax.jit(f)(x)

    def _builder(self, x):
        def g(v):
            return v
        self._exe = jax.jit(g)
        return self._exe(x)
'''
    reg = _fix_registry(tick_entries=("m:Sched._tick", "m:Sched._builder"))
    findings = hotpath_mod.analyze(_index(reg, m=src), reg)
    jits = [f for f in findings if f.rule == "hot-jit"]
    assert {f.func for f in jits} == {"m:Sched._tick"}  # memoized ok


# -- counters == spans --------------------------------------------------------

_COUNTER_SRC = '''
class GW:
    def bad(self):
        self.resilience.bump("retries")

    def good_inline(self):
        self.resilience.bump("hedges")
        self.tracer.record("r", "resilience", "gw", 0)

    def good_via_callee(self):
        self.resilience.bump("sheds")
        self._mark()

    def _mark(self):
        self.tracer.record("r", "resilience", "gw", 0)

    def other_family(self):
        self.metrics.bump("whatever")
'''


def test_counter_span_pairing():
    reg = _fix_registry()
    findings = counters_mod.analyze(_index(reg, m=_COUNTER_SRC), reg)
    assert {f.func for f in findings} == {"m:GW.bad"}
    assert all(f.rule == "counter-span" for f in findings)


# -- flag discipline ----------------------------------------------------------

_FLAG_CLI = '''
import argparse

from config import Cfg


def main(argv):
    parser = argparse.ArgumentParser(prog="x")
    parser.add_argument("--alpha", type=int, default=5)
    parser.add_argument("--beta", type=int, default=None)
    parser.add_argument("--gamma", action="store_true")
    parser.add_argument("--dead", type=int, default=0)
    args = parser.parse_args(argv)
    kw = {}
    if args.beta is not None:
        kw["beta"] = args.beta
    return Cfg(alpha=args.alpha, gamma=args.gamma, **kw)
'''

_FLAG_CONFIG = '''
import dataclasses


@dataclasses.dataclass
class Cfg:
    alpha: int = 7
    beta: int = 0
    gamma: bool = True
'''


def test_flag_analyzer_fixtures():
    reg = _fix_registry()
    findings = flags_mod.analyze(
        _index(reg, cli=_FLAG_CLI, config=_FLAG_CONFIG), reg)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # --alpha 5 threads unconditionally into Cfg.alpha (default 7).
    assert len(by_rule.get("flag-drift", [])) == 1
    assert "--alpha" in by_rule["flag-drift"][0].message
    # --gamma store_true lands on a default-True field.
    assert len(by_rule.get("flag-default-on", [])) == 1
    # --dead is parsed, never read.
    assert len(by_rule.get("flag-unwired", [])) == 1
    # --beta is conditionally threaded: clean despite default mismatch.
    assert not any("--beta" in f.message for f in findings)


def test_flag_analyzer_clean_and_unknown_field():
    reg = _fix_registry()
    cli = _FLAG_CLI.replace("default=5", "default=7") \
                   .replace('parser.add_argument("--dead", type=int, '
                            'default=0)\n    ', "") \
                   .replace("gamma=args.gamma, ", "")
    cfg = _FLAG_CONFIG.replace("gamma: bool = True",
                               "gamma: bool = False")
    findings = flags_mod.analyze(_index(reg, cli=cli, config=cfg), reg)
    assert [f for f in findings if f.rule != "flag-unwired"] == []
    cli_typo = cli.replace('kw["beta"]', 'kw["betaa"]')
    findings = flags_mod.analyze(_index(reg, cli=cli_typo, config=cfg),
                                 reg)
    assert "flag-unknown-field" in _rules(findings)


# -- baseline mechanics -------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    reg = _fix_registry()
    findings = locks_mod.analyze(_index(reg, m=_LOCK_VIOLATING), reg)
    assert findings
    path = str(tmp_path / "baseline.json")
    n = baseline_mod.save(findings + findings, path)  # dupes collapse
    assert n == len({f.key for f in findings})
    new, old = baseline_mod.split(findings, path)
    assert new == [] and len(old) == len(findings)
    with open(path, encoding="utf-8") as f:
        keys = json.load(f)["findings"]
    assert keys == sorted(set(keys))


def test_cli_rejects_rules_with_update_baseline(tmp_path, capsys):
    """A rule-filtered baseline rewrite would drop accepted findings of
    every other rule — the CLI must refuse the combination."""
    from tools.analyze.__main__ import main

    rc = main(["--rules", "hot-sync", "--update-baseline",
               "--baseline", str(tmp_path / "b.json")])
    assert rc == 2
    assert "cannot be combined" in capsys.readouterr().err


# -- regression tests for the findings fixed in this PR -----------------------

class _StubClient:
    """Minimal in-process lane: enough surface for Gateway dispatch."""

    def __init__(self):
        self.calls = 0

    def infer(self, payload):
        self.calls += 1
        return {"ok": True, "request_id": payload.get("request_id")}

    def health(self):
        return {"healthy": True}


def _stub_gateway(lanes):
    from tpu_engine.core.circuit_breaker import CircuitBreaker
    from tpu_engine.serving.gateway import Gateway

    gw = Gateway([])
    for name in lanes:
        gw._clients[name] = _StubClient()
        gw._breakers[name] = CircuitBreaker()
        gw._ring.add_node(name)
    return gw


def test_unknown_model_error_lists_served_models():
    """gateway.py `_route_inner` used to render the served-model list
    from `self._model_rings` AFTER releasing the gateway lock (lint:
    lock-unguarded) — the snapshot now happens under the lock, and the
    wire-visible error is unchanged."""
    from tpu_engine.core.consistent_hash import ConsistentHash

    gw = _stub_gateway(["w1"])
    ring = ConsistentHash(8)
    ring.add_node("w1")
    gw._model_rings["modela"] = ring
    gw._model_rings["modelb"] = ring
    gw.default_model = "modela"
    with pytest.raises(ValueError, match=r"unknown model 'nope'.*modela"):
        gw.route_request({"model": "nope", "input": [], "request_id": "r"})


def test_ejection_skip_and_all_ejected_fail_open():
    """gateway.py `_try_node` used to read `self._clients` OUTSIDE the
    lock when computing the fail-open peer set (lint: lock-unguarded).
    Behavior regression-pinned here: a partially-ejected ring skips the
    ejected lane; a fully-ejected ring fails open and still serves."""
    gw = _stub_gateway(["w1", "w2"])
    # Find a request id whose ring primary is w1, then eject w1: the
    # dispatch must skip it (w1.calls == 0) and serve from w2.
    rid = next(f"r{i}" for i in range(64)
               if gw._ring.get_node(f"r{i}") == "w1")
    gw._ejected.add("w1")
    out = gw.route_request({"request_id": rid, "input": []})
    assert out["ok"] is True
    assert gw._clients["w1"].calls == 0
    assert gw._clients["w2"].calls == 1
    # Every lane ejected: probe evidence alone must not produce an
    # outage — ejection is unhonored and the primary serves.
    gw._ejected.add("w2")
    out = gw.route_request({"request_id": rid, "input": []})
    assert out["ok"] is True
    assert gw._clients["w1"].calls == 1
