#!/usr/bin/env bash
# Environment bootstrap — the reference's setup.sh (pacman + vendored libs)
# equivalent. Nothing to download here (jax/flax/optax/orbax and the C++
# toolchain are baked into the image); this script builds the native core
# and smoke-checks the install.
set -euo pipefail
cd "$(dirname "$0")"

echo "== tpu_engine setup =="

# 1. Native C++ core (LRU cache, hash ring, breaker, batch queue): built by
# the loader itself from the tracked sources (g++ via native/build.sh),
# and rebuilt whenever they change.
python -c "from tpu_engine.core import native; assert native.available()"
echo "[1/3] native core built"

# 2. Python deps present?
python - <<'EOF'
import jax, flax, optax, orbax.checkpoint  # noqa: F401
print("[2/3] python deps ok (jax", jax.__version__ + ")")
EOF

# 3. Smoke: native bindings load + one CPU-mesh forward.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
from tpu_engine.core import native
from tpu_engine.models.registry import create_model, _ensure_builtin_models_imported
_ensure_builtin_models_imported()
spec = create_model("mlp")
params = spec.init(jax.random.PRNGKey(0))
out = spec.apply(params, jax.numpy.ones((1, spec.input_size)))
assert out.shape[0] == 1
print(f"[3/3] smoke ok (native core: {'loaded' if native.available() else 'python fallback'})")
EOF

echo "setup complete — try: python -m tpu_engine.serving.cli serve --model resnet50"
