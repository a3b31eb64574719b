"""The system under test, brought up the way a user's `serve` brings it up.

From the program the benchmark takes only: the entry point
(`serving.cli.select_platform`, `serving.app.serve_combined`), the model
registry, and the spans and counters the program keeps anyway. Nothing in
tpu_engine is changed, patched or configured through a flag of its own.

One configuration file (benchmarks/configs/<name>.json) gives the registry
factory and its keyword arguments (the sizes as they are run), the
WorkerConfig fields of the lane, and the reference's dialect.
"""

import dataclasses
import http.client
import json
import threading
import time


def seeded_key(seed):
    """A PRNG key from any whole number up to 2**63: JAX's own PRNGKey
    takes what an int32 holds."""
    import jax

    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)


def register_configuration(config, seed):
    """Register the configuration under its own name in the program's
    model registry (the CLI has no way to pass a factory's kwargs). The
    weights come from --seed, made on the device in one jitted call, in
    float32: the type the program keeps them in."""
    import jax

    from tpu_engine.models import registry

    registry._ensure_builtin_models_imported()
    name, factory, kwargs = config["name"], config["factory"], config["kwargs"]

    def make(**_ignored):
        spec = registry.create_model(factory, **kwargs)
        init = jax.jit(spec.init)
        return dataclasses.replace(
            spec, name=name, init=lambda _rng: init(seeded_key(seed)))

    registry.register(name)(make)
    return name


class Served:
    """The combined server of one cell: gateway, lanes, HTTP front."""

    def __init__(self, config, chips, seed):
        from tpu_engine.serving.app import serve_combined
        from tpu_engine.utils.config import GatewayConfig, WorkerConfig

        serving = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in config["serving"].items()}
        name = register_configuration(config, seed)
        # The Python front: the native front answers /generate/stream with
        # one complete buffer, so a client behind it sees no first token
        # (serving/app.py, _make_front_server).
        self.gateway, self.workers, self.server = serve_combined(
            model=name, lanes=chips, port=0,
            worker_config=WorkerConfig(model=name, **serving),
            gateway_config=GatewayConfig(port=0, **config.get("gateway", {})),
            warmup=False, native_front=False)
        self.port = self.server.port
        self.vocab = int(self.workers[0].engine.spec.config.vocab)

    def stop(self):
        for part in (self.server, *self.workers, self.gateway):
            part.stop()

    # -- what the program counts, read in-process -----------------------------

    def generator_stats(self):
        return {w.node_id: w.generator.stats() for w in self.workers}

    def spans(self):
        out = {w.node_id: w.tracer.snapshot() for w in self.workers}
        out["gateway"] = self.gateway.tracer.snapshot()
        return out

    def active_rows(self):
        return sum(s.get("active", 0) for s in self.generator_stats().values())

    def wait_idle(self, timeout_s):
        limit = time.monotonic() + timeout_s
        while time.monotonic() < limit:
            if self.active_rows() == 0:
                return True
            time.sleep(0.05)
        return False

    def memory_peak_bytes(self):
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))

    # -- blocking requests for warm-up and the correctness sample -------------

    def generate(self, rid, prompt, max_new_tokens, timeout_s=600):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout_s)
        try:
            conn.request("POST", "/generate", body=json.dumps({
                "request_id": rid, "prompt_tokens": prompt,
                "max_new_tokens": max_new_tokens}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise RuntimeError(f"/generate {rid} -> {resp.status}: "
                               f"{data[:300]!r}")
        return [int(t) for t in json.loads(data)["tokens"]]


class Sampler:
    """The traced run's thread: reads the lanes' pool counters every
    `period_s` (the program only keeps their current value), and starts and
    stops the profiler for the slice [begin, end) of time.monotonic().
    `traced` is the slice as it came out, on time.time(): from the
    profiler's start having returned to its stop being called."""

    def __init__(self, served, trace_dir, begin, end, period_s=0.5):
        self.served = served
        self.trace_dir, self.begin, self.end = trace_dir, begin, end
        self.period_s = period_s
        self.samples = []
        self.traced = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        state = "before"                      # -> "tracing" -> "done"
        while not self._stop.is_set():
            now = time.monotonic()
            if state == "before" and now >= self.begin:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.traced = {"begin": time.time(), "end": None}
                state = "tracing"
            elif state == "tracing" and now >= self.end:
                self._stop_trace()
                state = "done"
            stats = self.served.generator_stats()
            self.samples.append(
                {"t": now, "kv_pool": {node: s.get("kv_pool")
                                       for node, s in stats.items()}})
            self._stop.wait(self.period_s)
        if state == "tracing":
            self._stop_trace()

    def _stop_trace(self):
        import jax

        self.traced["end"] = time.time()
        jax.profiler.stop_trace()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=120)
