"""The MLP execution-time predictors (paper Sec. 3.4 / 4.3.3), in pure JAX.

Architecture (paper defaults): input layer -> 8 hidden layers x 1024 units,
ReLU -> 1 output (predicted fwd+bwd execution time in ms).  Trained with
Adam (lr 5e-4 -> 1e-4 after half the epochs), weight decay 1e-4, batch 512,
MAPE loss:

    L = mean( |pred - measured| / measured )

Layer count / width are configurable for the Fig. 5 sensitivity study.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import pickle
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import integrity
from repro.core.dataset import Dataset


@dataclasses.dataclass
class MLPConfig:
    in_features: int = 11
    hidden_layers: int = 8
    hidden_size: int = 1024
    epochs: int = 80
    batch_size: int = 512
    lr: float = 5e-4
    lr_after_half: float = 1e-4
    weight_decay: float = 1e-4
    seed: int = 0


def init_params(cfg: MLPConfig) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    key = jax.random.PRNGKey(cfg.seed)
    sizes = ([cfg.in_features] + [cfg.hidden_size] * cfg.hidden_layers + [1])
    params = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        scale = np.sqrt(2.0 / sizes[i])
        w = jax.random.normal(sub, (sizes[i], sizes[i + 1]),
                              jnp.float32) * scale
        b = jnp.zeros((sizes[i + 1],), jnp.float32)
        params.append((w, b))
    return params


def forward(params, x: jnp.ndarray) -> jnp.ndarray:
    """log(ms) per feature row.  Products run at ``Precision.HIGHEST``
    (a TPU's default is one bfloat16 pass), so the chip's answers agree
    with the fused scorer kernels and with a CPU run."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    h = x
    for w, b in params[:-1]:
        h = jax.nn.relu(dot(h, w) + b)
    w, b = params[-1]
    return (dot(h, w) + b)[..., 0]


#: jitted inference entry point: the fleet engine issues one batched
#: forward per op kind covering every destination device, so dispatch
#: overhead (not FLOPs) dominates without jit.  Shapes recompile per
#: (batch size, width) pair; the fleet path reuses a handful of shapes.
_forward_jit = jax.jit(forward)


def mape_loss(params, x, y) -> jnp.ndarray:
    """MAPE against raw times; the network predicts log(ms)."""
    pred = jnp.exp(forward(params, x))
    return jnp.mean(jnp.abs(pred - y) / jnp.maximum(y, 1e-9))


def male_loss(params, x, logy) -> jnp.ndarray:
    """Mean-absolute-log-error: the scale-free training surrogate.

    |log pred - log y| ≈ MAPE for small errors but is numerically stable
    across the ~6 orders of magnitude our op times span (stabilization
    choice on top of the paper's raw-MAPE; evaluation still reports MAPE)."""
    return jnp.mean(jnp.abs(forward(params, x) - logy))


#: monotonic TrainedMLP identity for result-cache keys.  ``id()`` is unsafe
#: here: CPython recycles addresses, so a retrained model could alias a
#: stale cache entry minted for its garbage-collected predecessor.
_UID = itertools.count()

#: finite ceiling for the network's log(ms) output.  float64 ``exp``
#: overflows to inf past ~709.78, and an inf prediction poisons every
#: downstream consumer (ranks, result caches, sqlite/netcache entries).
#: Out-of-distribution features must saturate to a huge-but-finite time
#: (e^80 ~ 5.5e34 ms — last in any ranking) instead.  In-distribution
#: log(ms) sits in roughly [-7, 12], so the clamp never moves a sane
#: prediction.
LOG_MS_MAX = 80.0


@dataclasses.dataclass
class TrainedMLP:
    kind: str
    cfg: MLPConfig
    params: list
    feature_mean: np.ndarray
    feature_std: np.ndarray
    test_mape: float = float("nan")
    uid: int = dataclasses.field(default_factory=lambda: next(_UID))

    def normalize(self, features: np.ndarray) -> np.ndarray:
        """Standardize raw feature rows with this model's train-set stats.

        Shared by the per-kind inference path and the fused multi-kind
        scorer (``core.batched.FusedMLPScorer``) so the two cannot drift."""
        return ((np.atleast_2d(features) - self.feature_mean)
                / self.feature_std)

    @staticmethod
    def ms_from_log(log_ms: np.ndarray) -> np.ndarray:
        """Map the network's log(ms) output to clamped milliseconds —
        the one output contract for every inference path.

        Clamped on both ends: ``LOG_MS_MAX`` keeps extreme features from
        overflowing ``exp`` into inf (which would poison ranks and
        result caches), and the 1e-6 floor keeps a negative blow-up from
        predicting zero time."""
        return np.maximum(np.exp(np.minimum(log_ms, LOG_MS_MAX)), 1e-6)

    def predict_ms(self, features: np.ndarray) -> np.ndarray:
        x = self.normalize(features)
        # bucket the batch size so the jitted forward compiles a bounded
        # set of shapes, not one per distinct trace: powers of two up to
        # 512, multiples of 512 beyond (keeps padding waste under ~20%
        # for the large fleet-grid batches)
        n = x.shape[0]
        if n <= 512:
            padded = 1 << max(n - 1, 0).bit_length()
        else:
            padded = -(-n // 512) * 512
        if padded != n:
            x = np.concatenate(
                [x, np.zeros((padded - n, x.shape[1]), x.dtype)])
        out = np.asarray(_forward_jit(self.params,
                                      jnp.asarray(x, jnp.float32)))[:n]
        return self.ms_from_log(out)

    def to_device(self, device) -> "TrainedMLP":
        """The same model with its parameters committed to ``device``:
        its forwards, and a fused scorer packed from it, run there."""
        return dataclasses.replace(
            self, params=jax.device_put(self.params, device))

    def save(self, path: Path) -> None:
        path.parent.mkdir(parents=True, exist_ok=True)
        blob = {"kind": self.kind, "cfg": dataclasses.asdict(self.cfg),
                "params": [(np.asarray(w), np.asarray(b))
                           for w, b in self.params],
                "mean": self.feature_mean, "std": self.feature_std,
                "test_mape": self.test_mape}
        with open(path, "wb") as f:
            f.write(integrity.seal(pickle.dumps(blob)))

    @staticmethod
    def load(path: Path) -> "TrainedMLP":
        """Load a sealed artifact (``integrity.IntegrityError`` on a
        checksum mismatch — ``predictor.train_mlps`` treats that as
        missing and retrains).  Raw-pickle artifacts written before the
        integrity envelope existed (e.g. the CI artifact cache) still
        load; they are re-sealed the next time they are saved."""
        with open(path, "rb") as f:
            raw = f.read()
        if integrity.is_sealed(raw):
            blob = pickle.loads(integrity.unseal(raw))
        else:                           # legacy pre-envelope artifact
            blob = pickle.loads(raw)
        return TrainedMLP(
            kind=blob["kind"], cfg=MLPConfig(**blob["cfg"]),
            params=[(jnp.asarray(w), jnp.asarray(b))
                    for w, b in blob["params"]],
            feature_mean=blob["mean"], feature_std=blob["std"],
            test_mape=blob["test_mape"])


def _adam_init(params):
    zeros = lambda p: [(jnp.zeros_like(w), jnp.zeros_like(b)) for w, b in p]
    return zeros(params), zeros(params)


@jax.jit
def _train_step(params, m, v, x, logy, lr, wd, t):
    loss, grads = jax.value_and_grad(male_loss)(params, x, logy)
    b1, b2, eps = 0.9, 0.999, 1e-8
    new_params, new_m, new_v = [], [], []
    for (w, b), (gw, gb), (mw, mb), (vw, vb) in zip(params, grads, m, v):
        mw = b1 * mw + (1 - b1) * gw
        mb = b1 * mb + (1 - b1) * gb
        vw = b2 * vw + (1 - b2) * gw**2
        vb = b2 * vb + (1 - b2) * gb**2
        mhw, mhb = mw / (1 - b1**t), mb / (1 - b1**t)
        vhw, vhb = vw / (1 - b2**t), vb / (1 - b2**t)
        w = w - lr * (mhw / (jnp.sqrt(vhw) + eps) + wd * w)
        b = b - lr * mhb / (jnp.sqrt(vhb) + eps)
        new_params.append((w, b))
        new_m.append((mw, mb))
        new_v.append((vw, vb))
    return new_params, new_m, new_v, loss


def train(dataset: Dataset, cfg: Optional[MLPConfig] = None,
          verbose: bool = False) -> TrainedMLP:
    """Train one MLP predictor on one kernel-varying op's dataset."""
    cfg = cfg or MLPConfig()
    norm = dataset.normalized()
    train_ds, test_ds = norm.split(0.8, seed=cfg.seed)
    cfg = dataclasses.replace(cfg, in_features=train_ds.x.shape[1])
    params = init_params(cfg)
    m, v = _adam_init(params)
    n = len(train_ds.y)
    rng = np.random.default_rng(cfg.seed)
    logy = np.log(np.maximum(train_ds.y, 1e-9))
    step = 0
    for epoch in range(cfg.epochs):
        lr = cfg.lr if epoch < cfg.epochs // 2 else cfg.lr_after_half
        perm = rng.permutation(n)
        for start in range(0, n, cfg.batch_size):
            idx = perm[start:start + cfg.batch_size]
            step += 1
            params, m, v, loss = _train_step(
                params, m, v,
                jnp.asarray(train_ds.x[idx]), jnp.asarray(logy[idx]),
                jnp.float32(lr), jnp.float32(cfg.weight_decay),
                jnp.float32(step))
        if verbose and (epoch % 10 == 0 or epoch == cfg.epochs - 1):
            print(f"  [{dataset.kind}] epoch {epoch:3d} loss {float(loss):.4f}")
    test_mape = float(mape_loss(params, jnp.asarray(test_ds.x),
                                jnp.asarray(test_ds.y)))
    return TrainedMLP(kind=dataset.kind, cfg=cfg, params=params,
                      feature_mean=norm.feature_mean,
                      feature_std=norm.feature_std, test_mape=test_mape)
