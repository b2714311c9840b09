"""CPU tests of the granite4h-small configuration: its corpus against an
analytic count of the step's matmuls, and the scorer-padding reader.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import gzip
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import bench  # noqa: E402

CONFIG = bench.load_config("granite4h-small")


def _doc(batch, origin="V100"):
    path = HERE / "data" / "granite4h-small" / f"b{batch}.{origin}.json.gz"
    return json.loads(gzip.decompress(path.read_bytes()))


def _dot_flops(doc):
    return sum(op["cost"]["flops"] * op["multiplicity"] for op in doc["ops"]
               if op["name"] == "dot_general")


def forward_matmul_flops(cfg: dict, batch: int) -> dict:
    """Forward matmul FLOPs of one step, from the configuration file:
    each layer's mixer (Mamba-2 projections and the four chunked-SSD
    contractions; or the GQA projections and the causal blocks of
    1024 x 1024 the flash attention visits), the router, the held experts
    at the capacity rule and the shared expert; the tied head."""
    s = cfg["seq_len"]
    t = batch * s
    d = cfg["hidden_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    h, p, q = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_chunk_size"]
    chunks = s // q
    mamba = (2 * t * d * (2 * di + 2 * n + h) + 2 * t * di * d
             + 2 * batch * chunks * h * q * q * (n + p)     # scores, y_intra
             + 2 * 2 * batch * chunks * h * q * n * p)      # states, y_inter
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, block = d // heads, 1024
    nq = s // block
    causal_blocks = nq * (nq + 1) // 2
    attn = (2 * t * d * (heads + 2 * kv) * hd + 2 * t * heads * hd * d
            + 2 * 2 * batch * heads * hd * block * block * causal_blocks)
    experts = cfg["deployment"]["experts_routed"]
    k, f = cfg["num_experts_per_tok"], cfg["intermediate_size"]
    capacity = max(1, round(t * k / experts * cfg["capacity_factor"]))
    shared_down = 2 * t * cfg["shared_intermediate_size"] * d
    ffn = (2 * t * d * experts
           + 3 * 2 * cfg["num_local_experts"] * capacity * d * f
           + 3 * shared_down)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"layers": sum(mamba if kind == "mamba" else attn
                          for kind in kinds) + len(kinds) * ffn,
            "shared_down": len(kinds) * shared_down,
            "head": 2 * t * d * cfg["vocab_size"]}


@pytest.mark.parametrize("batch", CONFIG["batch_sizes"])
def test_corpus_matmul_flops_match_the_config(batch):
    """Forward, backward (x3 in all) and the forward that each layer's
    remat recomputes for its backward, less the shared expert's down
    projection there: no gradient reads its output, so XLA's trace of
    the recompute leaves it out."""
    fwd = forward_matmul_flops(CONFIG, batch)
    want = 3 * (fwd["layers"] + fwd["head"]) + fwd["layers"] \
        - fwd["shared_down"]
    for origin in ("V100", "tpu-v5e"):
        got = _dot_flops(_doc(batch, origin))
        assert abs(got - want) <= 0.01 * want, (origin, got, want)


def test_make_step_tracks_to_the_corpus():
    from benchmarks.chip.steps import granite4h_small
    from repro.core import OperationTracker

    step, params, data = granite4h_small.make_step(CONFIG, 1)
    trace = OperationTracker("V100").track(step, params, data)
    doc = _doc(1)
    assert len(trace.ops) == len(doc["ops"])
    assert not any(op.kind == "recurrent" or op.name in ("jit", "pjit")
                   for op in trace.ops)
    assert sum(op.cost.flops * op.multiplicity for op in trace.ops
               if op.name == "dot_general") == _dot_flops(doc)
    kinds = {op.kind for op in trace.ops if op.kernel_varying}
    assert kinds == {"linear", "bmm"}


def test_the_cut_keeps_every_width_and_one_period():
    mcfg = __import__("benchmarks.chip.steps.granite4h_small",
                      fromlist=["model_config"]).model_config(CONFIG)
    assert mcfg.layer_types == tuple(CONFIG["layer_types"][:10])
    assert mcfg.layer_types.count("mamba") == 9 * mcfg.layer_types.count(
        "attention") == 9
    assert (mcfg.d_model, mcfg.d_ff, mcfg.shared_ff, mcfg.top_k,
            mcfg.n_experts, mcfg.experts_held) == (4096, 768, 1536, 10, 72, 9)
    assert set(CONFIG["published"]) == set(CONFIG["reduced"])
    for key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key]
    assert CONFIG["published"]["num_hidden_layers"] == len(
        CONFIG["layer_types"])
    assert 2.0e9 < mcfg.n_params() < 2.1e9


def _pad_ctx(before, after):
    return {"stats_before": {"scorer_rows": before},
            "stats_after": {"scorer_rows": after},
            "metric": "scorer_pad_pct.rank"}


def test_the_scorer_padding_reader_reads_a_stats_pair():
    read = bench.metric_reader("scorer_pad_pct.rank").read
    ctx = _pad_ctx({"real": 1000, "padded": 24},
                   {"real": 9055, "padded": 3213})
    assert read(ctx) == pytest.approx(100.0 * 3189 / (8055 + 3189))
    # a program without the counters, and a window the scorer never ran
    assert read({"stats_before": {}, "stats_after": {},
                 "metric": "scorer_pad_pct.rank"}) is None
    assert read(_pad_ctx({"real": 5, "padded": 3},
                         {"real": 5, "padded": 3})) is None
    assert read({"metric": "scorer_pad_pct.rank"}) is None
