"""The fused scorer counts the rows it launches: real feature rows and the
zero rows that pad them to whole row blocks and the jit bucket
(``batched.SCORER_ROWS``, ``/stats`` ``scorer_rows``).  Counting leaves
the blocks, buckets and padding as they are."""

import dataclasses

import numpy as np
import pytest

from repro.core import mlp
from repro.core.batched import SCORER_ROWS, FusedMLPScorer
from repro.kernels.fused_mlp_score import bucket_blocks, bucket_rows

KINDS = ("bmm", "conv2d", "linear", "recurrent")


@pytest.fixture(scope="module")
def scorer():
    cfg = mlp.MLPConfig(in_features=13, hidden_layers=2, hidden_size=32)
    mlps = {}
    for i, kind in enumerate(KINDS):
        params = mlp.init_params(dataclasses.replace(cfg, seed=i))
        mlps[kind] = mlp.TrainedMLP(kind=kind, cfg=cfg, params=params,
                                    feature_mean=np.zeros(13),
                                    feature_std=np.ones(13))
    return FusedMLPScorer(mlps, impl="jnp")


def _rows(n, seed=0):
    return np.random.default_rng(seed).uniform(0, 5, (n, 13))


def _moved(fn):
    before = SCORER_ROWS.snapshot()
    fn()
    after = SCORER_ROWS.snapshot()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("sizes", [{"linear": 130},
                                   {"linear": 10, "bmm": 3},
                                   {"conv2d": 128}])
def test_score_ms_counts_block_and_bucket_padding(scorer, sizes):
    blocks = sum(-(-n // 128) for n in sizes.values())
    launched = bucket_blocks(blocks) * 128
    real = sum(sizes.values())
    moved = _moved(lambda: scorer.score_ms(
        {k: _rows(n) for k, n in sizes.items()}))
    assert moved == {"real": real, "padded": launched - real}


def test_score_rows_ms_counts_the_stacked_lowering(scorer):
    kind_ids = np.array([0] * 40 + [2] * 7 + [3] * 100, np.int32)
    moved = _moved(lambda: scorer.score_rows_ms(_rows(len(kind_ids)),
                                                kind_ids))
    assert moved == {"real": 147,
                     "padded": len(KINDS) * bucket_rows(100) - 147}


def test_score_rows_ms_counts_the_row_mapped_kernel(scorer):
    interp = FusedMLPScorer(scorer.mlps, impl="interpret")
    kind_ids = np.arange(200, dtype=np.int32) % len(KINDS)
    moved = _moved(lambda: interp.score_rows_ms(_rows(200), kind_ids))
    assert moved == {"real": 200, "padded": bucket_blocks(2) * 128 - 200}


def test_stats_show_the_scorer_rows():
    from repro.core import HabitatPredictor
    from repro.serve.service import PredictionService

    stats = PredictionService(predictor=HabitatPredictor(),
                              coalesce_window_ms=0.0).stats()
    assert set(stats["scorer_rows"]) == {"real", "padded"}
