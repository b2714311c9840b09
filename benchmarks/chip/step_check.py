"""Granite 4.0-H Small's cut training step on the chip, against its plain
reference: the check that the model code computes the published model at
the cell's sizes, and the step's measured time.

    python3 benchmarks/chip/step_check.py [--seed N] [--rehearse]

One process, batch 1 of ``configs/granite4h-small.json`` (4096 tokens,
bfloat16 weights drawn from the seed, token ids from the vocabulary
slice, labels the next token):

1. the program's loss and gradients: ``transformer.loss_fn`` under
   ``jax.value_and_grad``, the step of ``steps/granite4h_small.py``
   without its update;
2. the control: the same with every weight matrix rounded to float8
   (e4m3), the precision below the configuration's bfloat16;
3. the reference: ``granite_hybrid_ref.loss_and_grads_blocked`` in
   float32 at ``highest`` matmul precision on the same bfloat16 weights,
   a layer at a time and the SSD recurrence in blocks of 256 steps;
4. each relative error (loss, and the Frobenius norm of each picked
   gradient's difference over the reference's), limited by
   ``TOLERANCES``;
5. each layer of the program against the reference layer on the same
   input and cotangent (``layer_errors``), with the program's layer in
   the configuration's precision and, as the control, on float8 weights
   and input;
6. the whole step's time, update included and weights donated: the
   median of 10 after two warm-up steps.

Prints one JSON line; exits non-zero when the program fails a tolerance
or the control passes every one.  ``--rehearse`` runs a small config of
the same pattern on any platform.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: relative error limits of the program's bfloat16 step against the
#: float32 reference: the loss, the gradients ``granite_hybrid_ref.
#: picks_one_per_kind`` names (``step.*``) and, layer by layer on the
#: same input and cotangent, each kind's update, input gradient and
#: mixer gradient (``layer.*``).  bfloat16 keeps 8 significant bits; a
#: rounding then moves the routing of the tokens near a top-10 boundary
#: and the Mamba decays exp(dt A), |A| up to 16, so the whole step's
#: gradients move by tens of percent at random init while float8
#: weights (4 significant bits) move them by most of their norm.  The
#: layer-by-layer errors do not compound through the stack: on a v5e at
#: seed 1616000001 bfloat16 reads at most 0.0124 in the attention layer
#: and 0.0144 in the Mamba-2 layers, float8 at least 0.022 and 0.135.
TOLERANCES = {"loss": 1e-3,
              "step.mamba.in_proj": 0.5, "step.attention.wq": 0.5,
              "step.experts.w_down": 0.5, "step.router": 0.5,
              "step.shared.w_up": 0.5, "step.embed": 0.5,
              "layer.attention.update": 0.02,
              "layer.attention.input_grad": 0.02,
              "layer.attention.mixer_grad": 0.02,
              "layer.mamba.update": 0.08, "layer.mamba.input_grad": 0.08,
              "layer.mamba.mixer_grad": 0.08}
SSD_BLOCK = 256


def _rehearsal(mcfg):
    return dataclasses.replace(
        mcfg, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64, d_ff=64,
        shared_ff=128, ssm_state=32, ssm_head_dim=32, ssm_chunk=32,
        vocab_size=512, attn_chunk_q=64, attn_chunk_kv=64)


def to_fp8(tree):
    """Every matrix of ``tree`` rounded to float8 (e4m3), kept in its
    dtype."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda w: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                                   if w.ndim >= 2 else w), tree)


def layer_errors(params, mcfg, tokens, key, control=False) -> dict:
    """Each layer of the program (``transformer.granite_layer``, in the
    configuration's precision) against the reference layer in float32, on
    the same input (the program's own residual stream) and, backward, the
    same seeded cotangent: the relative error of the layer's update
    ``y - x``, of the input's gradient and of the layer's largest weight
    gradient (the mixer's input projection), worst over the layers of
    each kind.  Layer by layer, a rounding does not compound through the
    stack, nor through the routing of the layers after it.  ``control``:
    the program's layer gets its weights and its input rounded to float8,
    the reference the unrounded ones."""
    import jax
    import jax.numpy as jnp

    from repro.models import granite_hybrid_ref as ref
    from repro.models import transformer

    f32 = jnp.float32
    dtype = jnp.dtype(mcfg.param_dtype)

    def rel(a, b):
        a, b = jnp.asarray(a, f32), jnp.asarray(b, f32)
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))

    def mixer_in(g, kind):
        return g["mamba"]["in_proj"] if kind == "mamba" else g["wq"]

    def run(kind):
        def prog(blk, x, g):
            y, vjp = jax.vjp(lambda b, x: transformer.granite_layer(
                b, x, mcfg, kind), blk, x)
            gb, gx = vjp(g.astype(y.dtype))
            return y, gx, mixer_in(gb, kind)

        def reference(blk, x, g):
            blk, x = ref._f32(blk), x.astype(f32)
            y, vjp = jax.vjp(lambda b, x: ref.layer(b, x, mcfg, kind,
                                                    SSD_BLOCK), blk, x)
            gb, gx = vjp(g)
            return y, gx, mixer_in(gb, kind)

        return jax.jit(prog), jax.jit(reference)

    fns = {k: run(k) for k in set(mcfg.layer_types)}
    x = (params["embed"][tokens]
         * jnp.asarray(mcfg.embedding_multiplier, dtype))
    out = {}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(mcfg.layer_types[:mcfg.n_layers]):
            blk = ref.layer_of(params, mcfg, i)
            g = jax.random.normal(jax.random.fold_in(key, 100 + i), x.shape,
                                  f32)
            prog, reference = fns[kind]
            y, gx, gw = (prog(to_fp8(blk), to_fp8(x), g) if control
                         else prog(blk, x, g))
            y_r, gx_r, gw_r = reference(blk, x, g)
            e = {"update": rel(y.astype(f32) - x.astype(f32),
                               y_r - x.astype(f32)),
                 "input_grad": rel(gx, gx_r), "mixer_grad": rel(gw, gw_r)}
            for name, v in e.items():
                key_ = f"{kind}.{name}"
                out[key_] = max(out.get(key_, 0.0), v)
            x = y
            del blk, gx, gw, y_r, gx_r, gw_r
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1616000001)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.chip import bench
    from benchmarks.chip.steps import granite4h_small
    from repro.models import granite_hybrid_ref as ref
    from repro.models import transformer

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"step_check: JAX runs on {dev.platform!r}, "
                         f"not a TPU")
    cfg = bench.load_config("granite4h-small")
    mcfg = granite4h_small.model_config(cfg)
    seq = cfg["seq_len"]
    if args.rehearse:
        mcfg, seq = _rehearsal(mcfg), 256
    key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (1, seq + 1), 0,
                             mcfg.vocab_size)
    tokens, labels = ids[:, :-1], ids[:, 1:]
    batch = {"tokens": tokens, "labels": labels}
    init = jax.jit(lambda k: transformer.init_params(mcfg, k))
    picks = ref.picks_one_per_kind(mcfg)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "seed": args.seed, "n_params": mcfg.n_params(), "seq": seq}

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(p, mcfg, batch)[0]))

    def program(params):
        t0 = time.perf_counter()
        loss, grads = grad_fn(params)
        picked = {n: ref.grad_of(grads, mcfg, pk) for n, pk in picks.items()}
        jax.block_until_ready(picked)
        del grads
        return float(loss), picked, time.perf_counter() - t0

    params = init(key)
    loss, got, secs = program(params)
    out["program_s"] = secs
    t0 = time.perf_counter()
    ref_loss, want = ref.loss_and_grads_blocked(
        params, mcfg, tokens, labels, picks,
        ssd_block=None if args.rehearse else SSD_BLOCK)
    ref_loss = float(ref_loss)
    out["reference_s"] = time.perf_counter() - t0

    def errors(loss, grads):
        e = {"loss": abs(loss - ref_loss) / abs(ref_loss)}
        for name in picks:
            a = jnp.asarray(grads[name], jnp.float32)
            b = want[name]
            e[name] = float(jnp.linalg.norm((a - b).ravel())
                            / jnp.linalg.norm(b.ravel()))
        return e

    out["loss"] = {"program": loss, "reference": ref_loss}
    out["whole_step"] = errors(loss, got)
    del got
    out["layers"] = layer_errors(params, mcfg, tokens, key)
    out["layers_control_fp8"] = layer_errors(params, mcfg, tokens, key,
                                             control=True)
    # eager, a matrix at a time: a jitted round trip through float8 may
    # be folded away, and two copies of the weights do not fit
    leaves, tree = jax.tree.flatten(params)
    del params
    for i, w in enumerate(leaves):
        leaves[i] = to_fp8(w)
    params = jax.tree.unflatten(tree, leaves)
    del leaves
    c_loss, c_got, _ = program(params)
    out["loss"]["control_fp8"] = c_loss
    out["whole_step_control_fp8"] = errors(c_loss, c_got)
    del c_got, params

    lr = granite4h_small.LR
    step = jax.jit(lambda p: jax.tree.map(lambda w, g: w - lr * g, p,
                                          grad_fn(p)[1]), donate_argnums=0)
    params = init(key)
    times = []
    for i in range(12):
        t0 = time.perf_counter()
        params = jax.block_until_ready(step(params))
        if i >= 2:
            times.append(time.perf_counter() - t0)
    out["step_ms"] = {"median": 1e3 * statistics.median(times),
                      "min": 1e3 * min(times), "max": 1e3 * max(times),
                      "runs": len(times)}
    out["memory_peak_bytes"] = (dev.memory_stats() or {}).get(
        "peak_bytes_in_use")
    out["tolerances"] = TOLERANCES

    def flat(step, layers):
        return {"loss": step["loss"],
                **{f"step.{k}": v for k, v in step.items() if k != "loss"},
                **{f"layer.{k}": v for k, v in layers.items()}}

    program_errors = flat(out["whole_step"], out["layers"])
    control_errors = flat(out["whole_step_control_fp8"],
                          out["layers_control_fp8"])
    # a NaN reading fails both ways: not within, and not caught
    ok = all(program_errors[k] <= tol for k, tol in TOLERANCES.items())
    caught = any(control_errors[k] > tol for k, tol in TOLERANCES.items())
    out["program_within"], out["control_caught"] = ok, caught
    print(json.dumps(out), flush=True)
    if not (ok and caught):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
