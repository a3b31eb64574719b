"""YOLOv8-family detection models — the mixed-shape serving workload.

Reference counterpart: BASELINE.json config 4 ("YOLOv8n ONNX, mixed-shape
inputs stressing XLA shape-bucket compile cache"). The reference collapsed
dynamic ONNX dims to 1 (``/root/reference/src/inference_engine.cpp:46-51``)
and could not serve multiple resolutions at all; here the model is fully
convolutional — one set of params serves every input resolution divisible
by 32, and the engine compiles one executable per (shape bucket, batch
bucket) (``runtime.engine`` shape buckets).

Architecture (YOLOv8-style, TPU-first): Conv(+BN+SiLU) stem, C2f stages
(split + n bottlenecks + concat — all channel dims MXU-friendly), SPPF,
FPN+PAN neck over P3/P4/P5, decoupled box/cls head with DFL-style box
bins. Output per sample: (n_anchors, 4*reg_max + nc) raw head maps,
n_anchors = sum(H/8*W/8, H/16*W/16, H/32*W/32) — shape-dependent, which is
exactly what the shape-bucket compile cache must handle. NHWC activations,
HWIO kernels, bf16 matmul/f32 accumulate throughout (ops.nn conventions).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from tpu_engine.models.registry import ModelSpec, register
from tpu_engine.ops import nn


@dataclasses.dataclass(frozen=True)
class YoloConfig:
    num_classes: int = 80
    reg_max: int = 16
    # Per-stage output channels (v8n = width 0.25 of [64,128,256,512,1024]).
    widths: Tuple[int, ...] = (16, 32, 64, 128, 256)
    # C2f bottleneck counts per stage (v8n = depth 1/3 of [3,6,6,3]).
    depths: Tuple[int, ...] = (1, 2, 2, 1)

    @property
    def head_ch(self) -> int:
        return 4 * self.reg_max + self.num_classes


# -- blocks -------------------------------------------------------------------

def _conv_init(key, k: int, cin: int, cout: int):
    return {"conv": nn.conv_init(key, k, k, cin, cout),
            "bn": nn.batchnorm_init(cout)}


def _conv(p, x, stride=1, dtype=None):
    x = nn.conv2d(p["conv"], x, stride=stride, dtype=dtype)
    return nn.silu(nn.batchnorm(p["bn"], x))


def _bottleneck_init(key, c: int):
    k1, k2 = jax.random.split(key)
    return {"cv1": _conv_init(k1, 3, c, c), "cv2": _conv_init(k2, 3, c, c)}


def _bottleneck(p, x, dtype=None):
    return x + _conv(p["cv2"], _conv(p["cv1"], x, dtype=dtype), dtype=dtype)


def _c2f_init(key, cin: int, cout: int, n: int):
    kc1, kc2, kb = jax.random.split(key, 3)
    c = cout // 2
    return {
        "cv1": _conv_init(kc1, 1, cin, cout),
        "cv2": _conv_init(kc2, 1, (2 + n) * c, cout),
        "m": [_bottleneck_init(k, c) for k in jax.random.split(kb, n)],
    }


def _c2f(p, x, dtype=None):
    y = _conv(p["cv1"], x, dtype=dtype)
    a, b = jnp.split(y, 2, axis=-1)
    outs = [a, b]
    for bp in p["m"]:
        outs.append(_bottleneck(bp, outs[-1], dtype=dtype))
    return _conv(p["cv2"], jnp.concatenate(outs, axis=-1), dtype=dtype)


def _sppf_init(key, c: int):
    k1, k2 = jax.random.split(key)
    h = c // 2
    return {"cv1": _conv_init(k1, 1, c, h), "cv2": _conv_init(k2, 1, 4 * h, c)}


def _sppf(p, x, dtype=None):
    y = _conv(p["cv1"], x, dtype=dtype)
    p1 = nn.max_pool(y, 5, 1)
    p2 = nn.max_pool(p1, 5, 1)
    p3 = nn.max_pool(p2, 5, 1)
    return _conv(p["cv2"], jnp.concatenate([y, p1, p2, p3], axis=-1),
                 dtype=dtype)


def _upsample2x(x):
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, 2, w, 2, c))
    return x.reshape(b, 2 * h, 2 * w, c)


def _branch_init(key, cin: int, mid: int, cout: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"cv1": _conv_init(k1, 3, cin, mid),
            "cv2": _conv_init(k2, 3, mid, mid),
            "out": nn.conv_init(k3, 1, 1, mid, cout)}


def _branch(p, x, dtype=None):
    x = _conv(p["cv2"], _conv(p["cv1"], x, dtype=dtype), dtype=dtype)
    return nn.conv2d(p["out"], x, dtype=dtype)


# -- model --------------------------------------------------------------------

def yolo_init(key, cfg: YoloConfig):
    w, d = cfg.widths, cfg.depths
    ks = jax.random.split(key, 16)
    params = {
        "stem": _conv_init(ks[0], 3, 3, w[0]),                 # /2  (P1)
        "down1": _conv_init(ks[1], 3, w[0], w[1]),             # /4  (P2)
        "c2f1": _c2f_init(ks[2], w[1], w[1], d[0]),
        "down2": _conv_init(ks[3], 3, w[1], w[2]),             # /8  (P3)
        "c2f2": _c2f_init(ks[4], w[2], w[2], d[1]),
        "down3": _conv_init(ks[5], 3, w[2], w[3]),             # /16 (P4)
        "c2f3": _c2f_init(ks[6], w[3], w[3], d[2]),
        "down4": _conv_init(ks[7], 3, w[3], w[4]),             # /32 (P5)
        "c2f4": _c2f_init(ks[8], w[4], w[4], d[3]),
        "sppf": _sppf_init(ks[9], w[4]),
        # FPN (top-down)
        "fpn4": _c2f_init(ks[10], w[4] + w[3], w[3], d[3]),
        "fpn3": _c2f_init(ks[11], w[3] + w[2], w[2], d[3]),
        # PAN (bottom-up)
        "pan_d3": _conv_init(ks[12], 3, w[2], w[2]),
        "pan4": _c2f_init(ks[13], w[2] + w[3], w[3], d[3]),
        "pan_d4": _conv_init(ks[14], 3, w[3], w[3]),
        "pan5": _c2f_init(ks[15], w[3] + w[4], w[4], d[3]),
    }
    hk = jax.random.split(jax.random.fold_in(key, 1), 3)
    mid = max(w[2], cfg.head_ch // 4)
    params["head"] = [
        _branch_init(hk[0], w[2], mid, cfg.head_ch),
        _branch_init(hk[1], w[3], mid, cfg.head_ch),
        _branch_init(hk[2], w[4], mid, cfg.head_ch),
    ]
    return params


def yolo_apply(params, x, cfg: YoloConfig, dtype=jnp.bfloat16):
    """x: (B, H, W, 3) with H, W divisible by 32 → (B, n_anchors, head_ch).

    Raw multi-scale head maps flattened anchor-major (P3 rows, then P4,
    then P5) — the standard pre-NMS detection tensor.
    """
    x = x.astype(dtype)
    x = _conv(params["stem"], x, stride=2, dtype=dtype)
    x = _conv(params["down1"], x, stride=2, dtype=dtype)
    x = _c2f(params["c2f1"], x, dtype=dtype)
    x = _conv(params["down2"], x, stride=2, dtype=dtype)
    p3 = _c2f(params["c2f2"], x, dtype=dtype)
    x = _conv(params["down3"], p3, stride=2, dtype=dtype)
    p4 = _c2f(params["c2f3"], x, dtype=dtype)
    x = _conv(params["down4"], p4, stride=2, dtype=dtype)
    p5 = _sppf(params["sppf"], _c2f(params["c2f4"], x, dtype=dtype),
               dtype=dtype)

    # FPN top-down
    f4 = _c2f(params["fpn4"],
              jnp.concatenate([_upsample2x(p5), p4], axis=-1), dtype=dtype)
    f3 = _c2f(params["fpn3"],
              jnp.concatenate([_upsample2x(f4), p3], axis=-1), dtype=dtype)
    # PAN bottom-up
    n4 = _c2f(params["pan4"],
              jnp.concatenate([_conv(params["pan_d3"], f3, stride=2,
                                     dtype=dtype), f4], axis=-1), dtype=dtype)
    n5 = _c2f(params["pan5"],
              jnp.concatenate([_conv(params["pan_d4"], n4, stride=2,
                                     dtype=dtype), p5], axis=-1), dtype=dtype)

    outs = []
    for p, feat in zip(params["head"], (f3, n4, n5)):
        y = _branch(p, feat, dtype=dtype)  # (B, h, w, head_ch)
        b, h, w, c = y.shape
        outs.append(y.reshape(b, h * w, c))
    return jnp.concatenate(outs, axis=1).astype(jnp.float32)


def n_anchors(h: int, w: int) -> int:
    return (h // 8) * (w // 8) + (h // 16) * (w // 16) + (h // 32) * (w // 32)


def _make_spec(name: str, cfg: YoloConfig, size: int) -> ModelSpec:
    def init(rng):
        return yolo_init(rng, cfg)

    def apply(params, x, dtype=jnp.bfloat16):
        return yolo_apply(params, x, cfg, dtype=dtype)

    return ModelSpec(
        name=name,
        apply=apply,
        init=init,
        input_shape=(size, size, 3),
        output_shape=(n_anchors(size, size), cfg.head_ch),
        config=cfg,
        tp_rule="dense_output",  # conv kernels: the rank heuristic
    )


@register("yolov8n")
def make_yolov8n(size: int = 640, num_classes: int = 80) -> ModelSpec:
    return _make_spec("yolov8n", YoloConfig(num_classes=num_classes), size)


@register("yolov8n-small-test")
def make_yolo_small(size: int = 64, num_classes: int = 4) -> ModelSpec:
    """Tiny config for tests/CI — same code path, millisecond compiles."""
    cfg = YoloConfig(num_classes=num_classes, reg_max=4,
                     widths=(8, 8, 16, 16, 32), depths=(1, 1, 1, 1))
    return _make_spec("yolov8n-small-test", cfg, size)
