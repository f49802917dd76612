"""The comparison that decides `correct`: served answers against the reference.

The number compared is the logit gap: for each sampled request,
max |served - reference| / max |reference| over that request's logits
(cls: its C logits; seg: its n x C per-point logits), and the worst such
ratio over the sample. The reference is `bench/reference/<name>.py`
(`cell.load_reference`), run after the window on the same clouds, xyz and
per-point features, with weights made anew from the seed.
"""

from __future__ import annotations

import numpy as np

from benchlib import traffic


def sample_slots(sizes: list[int], count: int, seed: int) -> set[int]:
    """Pool slots whose answers are checked.

    `count` drawn from the seed, plus the largest and the smallest cloud.
    """
    rng = np.random.default_rng([seed, 7])
    n = len(sizes)
    picked = set(rng.choice(n, size=min(count, n), replace=False).tolist())
    return picked | {int(np.argmax(sizes)), int(np.argmin(sizes))}


def gap(served: np.ndarray, ref: np.ndarray) -> float:
    """max |served - ref| / max |ref| for one request."""
    served, ref = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    if served.shape != ref.shape:
        return float("inf")
    return float(np.abs(served - ref).max() / max(np.abs(ref).max(), 1e-30))


def reference_answers(ref, model: dict, seed: int, clouds: dict[int, np.ndarray], *,
                      quant: str, passes: int, block: int) -> dict[int, np.ndarray]:
    """Reference logits of each cloud, computed in blocks of `block` clouds.

    Each cloud is fitted to (n_points, width) as serving fits it, where
    width is the clouds' channels per point: xyz and any features.
    """
    import jax

    params = jax.jit(lambda k: ref.init_params(k, model))(traffic.jax_key(seed))
    fn = ref.make_block_fn(model, quant=quant, passes=passes)
    n_points = model["n_points"]
    slots = sorted(clouds)
    out: dict[int, np.ndarray] = {}
    for lo in range(0, len(slots), block):
        part = slots[lo:lo + block]
        fitted = np.zeros((block, n_points, clouds[part[0]].shape[1]), np.float32)
        for i, s in enumerate(part):
            fitted[i] = clouds[s][ref.fit_rows(len(clouds[s]), n_points)]
        logits = np.asarray(fn(params, fitted))
        for i, s in enumerate(part):
            if model["task"] == "seg":
                out[s] = logits[i][ref.output_rows(len(clouds[s]), n_points)]
            else:
                out[s] = logits[i]
    return out


def worst_gap(served: dict[int, np.ndarray], refs: dict[int, np.ndarray]) -> float:
    """The widest gap over every sampled request; inf where none answered."""
    gaps = [gap(served[s], refs[s]) for s in refs if s in served]
    return max(gaps) if gaps else float("inf")
