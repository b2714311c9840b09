"""ResNet-50's training step as He et al. publish it (arXiv:1512.03385,
Table 1 and Sec. 3.3-3.4): what a user traces and sends the service.

* stem: 7x7/2 conv, 64 channels, batch norm, ReLU, 3x3/2 max-pool;
* four stages of bottleneck blocks, (3, 4, 6, 3) of them, widths 64, 128,
  256 and 512 with expansion 4 (1x1 reduce, 3x3, 1x1 expand), batch norm
  after every conv and ReLU after the sum;
* the first block of a stage has a projection shortcut (option B, 1x1 conv
  and batch norm); stages 2-4 downsample with stride 2 in that block's
  first 1x1 conv, as the paper's model does;
* global average pool, a 1000-way fully connected layer with bias,
  softmax cross-entropy;
* float32, NCHW, batch norm over the batch (training mode); one SGD step
  (forward, backward and the update) is the iteration Habitat traces.

``make_step(config, batch)`` returns ``(step, params, data)`` with
``params`` and ``data`` as shapes only: the corpus is tracked, not run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LR = 1e-3


def _conv(x, w, stride=1):
    k = w.shape[-1]
    pad = ((k - 1) // 2, (k - 1) // 2)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), (pad, pad),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(x, p):
    mean = x.mean((0, 2, 3), keepdims=True)
    var = x.var((0, 2, 3), keepdims=True)
    xn = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    return xn * p["g"][None, :, None, None] + p["b"][None, :, None, None]


def _max_pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))


def _shapes(cfg: dict) -> dict:
    """Parameter shapes: conv weights OIHW, a (gamma, beta) per norm."""
    f32 = jnp.float32

    def conv(o, i, k):
        return jax.ShapeDtypeStruct((o, i, k, k), f32)

    def norm(c):
        return {"g": jax.ShapeDtypeStruct((c,), f32),
                "b": jax.ShapeDtypeStruct((c,), f32)}

    expansion = cfg["expansion"]
    stem = cfg["stem_channels"]
    params = {"stem": conv(stem, cfg["in_channels"], cfg["stem_kernel"]),
              "stem_bn": norm(stem)}
    cin = stem
    for s, (n, width) in enumerate(zip(cfg["blocks"], cfg["widths"])):
        out = width * expansion
        for b in range(n):
            blk = {"w1": conv(width, cin, 1), "n1": norm(width),
                   "w2": conv(width, width, 3), "n2": norm(width),
                   "w3": conv(out, width, 1), "n3": norm(out)}
            if b == 0:
                blk["proj"] = conv(out, cin, 1)
                blk["nproj"] = norm(out)
            params[f"s{s}b{b}"] = blk
            cin = out
    params["fc_w"] = jax.ShapeDtypeStruct((cin, cfg["classes"]), f32)
    params["fc_b"] = jax.ShapeDtypeStruct((cfg["classes"],), f32)
    return params


def make_step(cfg: dict, batch: int):
    blocks = cfg["blocks"]

    def forward(params, x):
        h = jax.nn.relu(_bn(_conv(x, params["stem"], 2), params["stem_bn"]))
        h = _max_pool(h)
        for s, n in enumerate(blocks):
            for b in range(n):
                p = params[f"s{s}b{b}"]
                stride = 2 if (b == 0 and s > 0) else 1
                r = jax.nn.relu(_bn(_conv(h, p["w1"], stride), p["n1"]))
                r = jax.nn.relu(_bn(_conv(r, p["w2"]), p["n2"]))
                r = _bn(_conv(r, p["w3"]), p["n3"])
                sc = (_bn(_conv(h, p["proj"], stride), p["nproj"])
                      if b == 0 else h)
                h = jax.nn.relu(r + sc)
        return h.mean((2, 3)) @ params["fc_w"] + params["fc_b"]

    def loss(params, data):
        logits = forward(params, data["x"])
        onehot = jax.nn.one_hot(data["y"], logits.shape[-1])
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    def step(params, data):
        value, grads = jax.value_and_grad(loss)(params, data)
        return value, jax.tree.map(lambda p, g: p - LR * g, params, grads)

    image = cfg["image"]
    data = {"x": jax.ShapeDtypeStruct((batch, cfg["in_channels"], image,
                                       image), jnp.float32),
            "y": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    return step, _shapes(cfg), data
