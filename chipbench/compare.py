"""The comparisons that decide ``correct``: output against reference."""
from __future__ import annotations

import numpy as np

# entries near zero (image pixels where the noise crosses zero, Gaussian
# tails that underflow on one side) are compared against this share of
# their field's largest value instead of against themselves
FLOOR = 1e-3


def gap(got: dict, ref: dict, floor: float = FLOOR) -> dict:
    """``value_gap``: the largest |got - ref| / (|ref| + floor * peak) over
    the finite entries of every field (``peak``: the field's largest finite
    magnitude in ``ref``); ``nan_mismatch``: entries finite on one side
    only."""
    worst, mismatch = 0.0, 0
    for k in ref:
        a = np.asarray(got[k], np.float64)
        b = np.asarray(ref[k], np.float64)
        if a.shape != b.shape:
            raise ValueError(f"{k}: shape {a.shape} != reference {b.shape}")
        fa, fb = np.isfinite(a), np.isfinite(b)
        mismatch += int((fa != fb).sum())
        both = fa & fb
        if not both.any():
            continue
        a, b = a[both], b[both]
        peak = float(np.abs(b).max())
        denom = np.abs(b) + floor * peak
        d = np.abs(a - b)
        with np.errstate(divide="ignore", invalid="ignore"):
            e = np.where(denom > 0, d / denom, np.where(d > 0, np.inf, 0.0))
        worst = max(worst, float(e.max()))
    return {"value_gap": worst, "nan_mismatch": mismatch}


def one_at_a_time(fn, ids: np.ndarray, u: np.ndarray) -> dict:
    """``fn(u_row, sample_id)`` for each sample in turn on the host CPU,
    matrix products at ``highest``; outputs stacked as float64."""
    import jax
    cpu = jax.devices("cpu")[0]
    rows = []
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        for sid, row in zip(ids, u):
            out = fn(jax.device_put(np.asarray(row, np.float32), cpu),
                     jax.device_put(np.uint32(sid), cpu))
            rows.append({k: np.asarray(v, np.float64) for k, v in out.items()})
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}
