"""Plain float64 reference of a served prediction, and the benchmark's MLPs.

Independent of the program: it reads trace documents as JSON, the device
table of ``devices.json`` and the MLP weights that :func:`make_mlps` draws
from the seed, and imports nothing of ``repro``.  It follows Habitat's
definition of a prediction (arXiv:2102.00527, Sec. 3.3-3.4), as the
program states it:

* a kernel-alike op's origin time is scaled to each device by wave scaling
  (Eq. 2, gamma from Eq. 3 and the device's ridge point);
* a kernel-varying op (conv2d, linear, bmm, recurrent) is priced by its
  kind's MLP on ``log1p`` of the op's and the device's features,
  standardised, 3 hidden ReLU layers, output log(ms) clamped to
  [1e-6 ms, e^80 ms];
* an iteration is the sum of its ops' times times their multiplicity.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent
MLP_KINDS = ("bmm", "conv2d", "linear", "recurrent")
HIDDEN, HIDDEN_LAYERS = 256, 3
N_FEATURES = 13                     # 9 of the op, 4 of the device
LOG_MS_MAX = 80.0


def device_table() -> List[dict]:
    """The 15 devices a served answer covers, sorted by name."""
    doc = json.loads((HERE / "devices.json").read_text())
    return sorted(doc["devices"], key=lambda d: d["name"])


# -- features ----------------------------------------------------------------
def _op_features(op: dict) -> List[float]:
    p, kind = op["params"], op["kind"]
    flops = float(op["cost"]["flops"])
    nbytes = float(op["cost"]["bytes_read"]) + float(op["cost"]["bytes_written"])
    if kind == "conv2d":
        f = [p.get("batch", 1), p.get("in_ch", 1), p.get("out_ch", 1),
             p.get("kernel", 1), p.get("padding", 0), p.get("stride", 1),
             p.get("image", 1)]
    elif kind == "linear":
        f = [p.get("batch", 1), p.get("in_f", 1), p.get("out_f", 1),
             p.get("bias", 0), 0, 0, 0]
    elif kind == "bmm":
        f = [p.get("b", 1), p.get("m", 1), p.get("n", 1), p.get("k", 1),
             0, 0, 0]
    elif kind == "recurrent":
        f = [p.get("batch", 1), p.get("in_f", 1), p.get("hidden", 1),
             p.get("seq", 1), p.get("layers", 1), p.get("bidir", 0),
             p.get("bias", 0)]
    else:
        raise ValueError(f"{kind} has no MLP features")
    return [float(x) for x in f] + [flops, nbytes]


def _device_features(dev: dict) -> List[float]:
    return [dev["mem_capacity"] / 2**30, dev["mem_bandwidth"] / 1e9,
            float(dev["num_units"]), dev["peak_flops"] / 1e12]


class Doc:
    """One trace document reduced to what a prediction needs."""

    def __init__(self, doc: dict):
        ops = doc["ops"]
        self.label = doc["label"]
        self.origin = doc["origin_device"]
        self.kinds = [op["kind"] for op in ops]
        self.mult = np.array([op["multiplicity"] for op in ops], np.float64)
        self.measured = np.array([op["measured_ms"] for op in ops],
                                 np.float64)
        self.flops = np.array([op["cost"]["flops"] for op in ops],
                              np.float64)
        self.nbytes = np.array([op["cost"]["bytes_read"]
                                + op["cost"]["bytes_written"] for op in ops],
                               np.float64)
        self.varying = np.array([k in MLP_KINDS for k in self.kinds])
        self.mlp_rows = {k: [i for i, kk in enumerate(self.kinds) if kk == k]
                         for k in MLP_KINDS}
        self.op_feats = {k: np.array([_op_features(ops[i]) for i in rows],
                                     np.float64).reshape(len(rows), 9)
                         for k, rows in self.mlp_rows.items()}

    @property
    def n_varying(self) -> int:
        return int(self.varying.sum())


def feature_rows(doc: Doc, kind: str, devs: Sequence[dict]) -> np.ndarray:
    """Raw features of ``kind``'s ops on every device, (n_ops * n_dev, 13),
    op-major, before ``log1p``."""
    of = doc.op_feats[kind]
    df = np.array([_device_features(d) for d in devs], np.float64)
    return np.concatenate([np.repeat(of, len(devs), axis=0),
                           np.tile(df, (len(of), 1))], axis=1)


# -- the benchmark's MLPs ----------------------------------------------------
def feature_stats(docs: Sequence[Doc], devs: Sequence[dict]) -> dict:
    """Per-kind mean and spread of ``log1p`` features over a corpus: the
    standardisation the benchmark's MLPs use (spread floored at 1)."""
    out = {}
    for kind in MLP_KINDS:
        rows = [np.log1p(feature_rows(d, kind, devs)) for d in docs
                if d.op_feats[kind].shape[0]]
        if rows:
            x = np.concatenate(rows)
            mean, std = x.mean(0), x.std(0)
        else:
            mean, std = np.zeros(N_FEATURES), np.ones(N_FEATURES)
        out[kind] = (mean, np.where(std < 1.0, 1.0, std))
    return out


def make_mlps(seed: int, stats: dict) -> Dict[str, dict]:
    """Four MLPs of the program's shape (13 -> 3 x 256 -> 1), float32
    weights drawn from ``seed``: He-normal matrices, small biases."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    sizes = [N_FEATURES] + [HIDDEN] * HIDDEN_LAYERS + [1]
    out = {}
    for kind in MLP_KINDS:
        ws, bs = [], []
        for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
            ws.append((rng.standard_normal((fan_in, fan_out))
                       * math.sqrt(2.0 / fan_in)).astype(np.float32))
            bs.append((0.1 * rng.standard_normal(fan_out))
                      .astype(np.float32))
        mean, std = stats[kind]
        out[kind] = {"w": ws, "b": bs, "mean": mean, "std": std}
    return out


def save_mlps(path: Path, mlps: Dict[str, dict]) -> None:
    arrays = {}
    for kind, m in mlps.items():
        for i, (w, b) in enumerate(zip(m["w"], m["b"])):
            arrays[f"{kind}.w{i}"] = w
            arrays[f"{kind}.b{i}"] = b
        arrays[f"{kind}.mean"] = m["mean"]
        arrays[f"{kind}.std"] = m["std"]
    np.savez(path, **arrays)


def load_mlps(path: Path) -> Dict[str, dict]:
    z = np.load(path)
    out = {}
    for kind in MLP_KINDS:
        n = len([k for k in z.files if k.startswith(f"{kind}.w")])
        out[kind] = {"w": [z[f"{kind}.w{i}"] for i in range(n)],
                     "b": [z[f"{kind}.b{i}"] for i in range(n)],
                     "mean": z[f"{kind}.mean"], "std": z[f"{kind}.std"]}
    return out


def mlp_forward_f64(m: dict, x: np.ndarray) -> np.ndarray:
    """log(ms) of standardised rows ``x``, in float64."""
    h = x
    for i, (w, b) in enumerate(zip(m["w"], m["b"])):
        h = h @ np.asarray(w, np.float64) + np.asarray(b, np.float64)
        if i < len(m["w"]) - 1:
            h = np.maximum(h, 0.0)
    return h[:, 0]


def ms_from_log(log_ms: np.ndarray) -> np.ndarray:
    return np.maximum(np.exp(np.minimum(log_ms, LOG_MS_MAX)), 1e-6)


def mlp_inputs(doc: Doc, kind: str, devs, m: dict) -> np.ndarray:
    return (np.log1p(feature_rows(doc, kind, devs)) - m["mean"]) / m["std"]


def mlp_part(doc: Doc, devs, mlps, forward=mlp_forward_f64) -> np.ndarray:
    """Per-device ms of the kernel-varying ops, (n_dev,): the part of an
    answer the MLP scorer computes.  ``forward`` maps standardised rows to
    log(ms); the control passes a lower-precision one."""
    total = np.zeros(len(devs))
    for kind, rows in doc.mlp_rows.items():
        if not rows:
            continue
        m = mlps[kind]
        ms = ms_from_log(forward(m, mlp_inputs(doc, kind, devs, m)))
        ms = ms.reshape(len(rows), len(devs))
        total += (ms * doc.mult[rows][:, None]).sum(0)
    return total


# -- wave scaling ------------------------------------------------------------
def wave_factors(doc: Doc, devs: Sequence[dict]) -> np.ndarray:
    """(n_ops, n_dev) Eq. 2 factors from the origin to each device."""
    by_name = {d["name"]: d for d in device_table()}
    o = by_name[doc.origin]
    x = doc.flops / np.maximum(doc.nbytes, 1.0)
    out = np.empty((len(x), len(devs)))
    for j, d in enumerate(devs):
        r = d["peak_flops"] / d["mem_bandwidth"]
        g = np.where(x <= 0.0, 1.0,
                     np.where(x < r, 1.0 - 0.5 * x / r,
                              0.5 * r / np.where(x > 0.0, x, 1.0)))
        d_ratio = o["mem_bandwidth"] / d["mem_bandwidth"]
        c_ratio = o["clock_hz"] / d["clock_hz"]
        w_ratio = ((o["num_units"] * o["tiles_per_unit"])
                   / (d["num_units"] * d["tiles_per_unit"]))
        out[:, j] = d_ratio ** g * w_ratio ** (1 - g) * c_ratio ** (1 - g)
    return out


class Predictor:
    """Reference answers for the traces of one base document: the wave
    factors and the MLP part do not depend on the measured times, so they
    are computed once and each jittered copy costs one product."""

    def __init__(self, doc: Doc, mlps, devs=None):
        self.devs = list(devs or device_table())
        self.doc = doc
        self.mlp_ms = mlp_part(doc, self.devs, mlps)
        alike = ~doc.varying
        self._alike_factor = (wave_factors(doc, self.devs)[alike]
                              * doc.mult[alike][:, None])
        self._alike = alike

    def iter_ms(self, measured: np.ndarray) -> np.ndarray:
        """Predicted iteration ms per device for the op times ``measured``."""
        return measured[self._alike] @ self._alike_factor + self.mlp_ms
