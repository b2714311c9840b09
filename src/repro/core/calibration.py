"""Real wall-clock measurement of tracked ops on the device JAX runs on.

This is the genuinely *runtime-based* half of the reproduction: the paper
measures each operation's execution time on the GPU the user already has by
re-running it in isolation (Sec. 4.1, "Execution time").  Here that device
is whatever this process runs JAX on — a TPU chip, or the host CPU — and a
trace is only measured on the device its ``origin_device`` names.  Each
tracked op is rebuilt as a standalone jitted callable with the recorded
shapes and dtype, on operands made on the device, and timed with the paper's
protocol (3 discarded warm-up runs, then the average of 3 measured runs).

An op ``build_callable`` cannot rebuild is priced by the simulator with
the origin's spec instead, and counts against the coverage that
``measure_trace_inplace`` returns.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import devices, simulator
from repro.core.trace import Op, TrackedTrace

WARMUP = 3
REPS = 3

_ELEMENTWISE = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "max": jnp.maximum, "min": jnp.minimum,
    "pow": jnp.power,
}
_UNARY = {
    "exp": jnp.exp, "log": jnp.log, "tanh": jnp.tanh, "neg": jnp.negative,
    "rsqrt": jax.lax.rsqrt, "sqrt": jnp.sqrt, "logistic": jax.nn.sigmoid,
    "erf": jax.lax.erf, "abs": jnp.abs, "sign": jnp.sign,
    "integer_pow": lambda x: x * x, "cos": jnp.cos, "sin": jnp.sin,
}


def _time_callable(fn: Callable, *args) -> float:
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    for _ in range(WARMUP - 1):
        jax.block_until_ready(jfn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        jax.block_until_ready(jfn(*args))
    return (time.perf_counter() - t0) / REPS * 1e3  # ms


@partial(jax.jit, static_argnums=(0, 1))
def _rand_on_device(shape: Tuple[int, ...], dtype: str):
    key = jax.random.key(0)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return jax.random.normal(key, shape, dtype)
    return jax.random.bernoulli(key, 0.5, shape).astype(dtype)


def _rand(shape, dtype="float32"):
    """Standard-normal (floats) or 0/1 (integers, bools) operand, made on
    the device: a host float64 RNG made embedding-sized operands the
    largest cost of a full-width trace's measurement on a TPU."""
    return _rand_on_device(tuple(int(d) for d in shape), str(dtype))


def build_callable(op: Op) -> Optional[Tuple[Callable, tuple]]:
    """Rebuild a representative standalone callable for ``op``."""
    p = op.params
    if op.kind == "linear":
        a = _rand((p["m"], p["k"]), op.dtype)
        b = _rand((p["k"], p["n"]), op.dtype)
        return jnp.matmul, (a, b)
    if op.kind == "bmm":
        a = _rand((p["b"], p["m"], p["k"]), op.dtype)
        b = _rand((p["b"], p["k"], p["n"]), op.dtype)
        return jnp.matmul, (a, b)
    if op.kind == "conv2d":
        out_size = ((p["image"] + 2 * p["padding"] - p["kernel"])
                    // p["stride"] + 1)
        if (not op.in_shapes or len(op.in_shapes[0]) != 4
                or out_size < 1):
            return None     # not a 2-D convolution this rebuild covers
        x = _rand((p["batch"], p["in_ch"], p["image"], p["image"]))
        w = _rand((p["out_ch"], p["in_ch"], p["kernel"], p["kernel"]))
        fn = partial(jax.lax.conv_general_dilated,
                     window_strides=(p["stride"], p["stride"]),
                     padding=[(p["padding"], p["padding"])] * 2)
        return fn, (x, w)
    if op.kind == "recurrent":
        x = _rand((p["seq"], p["batch"], p["in_f"]))
        w = _rand((p["in_f"] + p["hidden"], p["hidden"]))
        h0 = _rand((p["batch"], p["hidden"]))

        def rnn(x, w, h0):
            def step(h, xt):
                h = jnp.tanh(jnp.concatenate([xt, h], -1) @ w)
                return h, h
            return jax.lax.scan(step, h0, x)
        return rnn, (x, w, h0)
    if op.name in _UNARY and op.in_shapes:
        return _UNARY[op.name], (_rand(op.in_shapes[0], op.dtype),)
    if op.name in _ELEMENTWISE and len(op.in_shapes) >= 2:
        return _ELEMENTWISE[op.name], (_rand(op.in_shapes[0], op.dtype),
                                       _rand(op.in_shapes[1], op.dtype))
    if op.name.startswith("reduce_") and op.in_shapes:
        return jnp.sum, (_rand(op.in_shapes[0], op.dtype),)
    return None


def measure_op_ms(op: Op, origin: devices.DeviceSpec) -> Tuple[float, bool]:
    """(ms, measured_for_real) for one op on this process's device.

    An op without a rebuild is simulated with the ``origin`` spec — the
    device the trace claims — and reported as not measured."""
    built = build_callable(op)
    if built is None:
        return simulator.op_time_ms(op, origin), False
    fn, args = built
    return _time_callable(fn, *args), True


def measure_trace_inplace(trace: TrackedTrace) -> float:
    """Fill ``measured_ms`` on every op by timing it on this device.

    Raises ``ValueError`` when ``trace.origin_device`` is not the device
    this process runs JAX on: times taken here would be labelled as
    another device's.  Returns the fraction of iteration time covered by
    real measurements."""
    here = devices.local_device()
    if trace.origin_device != here:
        raise ValueError(
            f"trace origin {trace.origin_device!r} is not the device this "
            f"process runs on ({here!r}); wallclock measurement times ops "
            f"on the device at hand")
    origin = devices.get(trace.origin_device)
    real_ms = total_ms = 0.0
    for op in trace.ops:
        ms, real = measure_op_ms(op, origin)
        op.measured_ms = ms
        total_ms += ms * op.multiplicity
        if real:
            real_ms += ms * op.multiplicity
    return real_ms / max(total_ms, 1e-12)


def calibrate_host_spec() -> dict:
    """Measure the host's achieved GEMM rate and memory bandwidth.

    Habitat ships measured bandwidths in its config file (Sec. 3.3); this is
    the equivalent measurement pass for the host device."""
    n = 1024
    a = _rand((n, n))
    gemm_ms = _time_callable(jnp.matmul, a, a)
    flops = 2.0 * n**3 / (gemm_ms * 1e-3)
    big = _rand((64 * 1024 * 1024 // 4,))  # 64 MiB
    copy_ms = _time_callable(lambda x: x + 1.0, big)
    bw = 2.0 * big.size * 4 / (copy_ms * 1e-3)
    return {"peak_flops": flops, "mem_bandwidth": bw}
