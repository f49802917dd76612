"""Work functions: the operations and bytes each kernel call and step needs.

They count the algorithm's work from the configuration's shapes, not what
one implementation does: a W16A16 product of (M, K) by (K, N) is 2*M*K*N
integer operations on 16-bit operands, however many partial products a
kernel forms. The least time of a call is the larger of its operations over
the chip's compute peak and its bytes over the HBM bandwidth; `bound` names
which. The preprocess kernels run on the vector unit, for which no peak is
published; the bf16 peak stands in, so their shares are upper bounds.

Operation counts, per call:

* fps, per tile of P points drawing k samples: k - 1 steps, each an L1
  distance (3 sub, 3 abs, 2 add), a min and a max compare per point: 10*P.
  Bytes: the tile read once (12 B a point), k indices written (4 B each).
* lattice, per tile with K centroids over P points: an L1 distance and a
  threshold per pair (9 ops). Bytes: points and centroids read (12 B
  each), K * nsample indices (4 B) and mask bytes (1 B) written.
* sc_matmul: 2*M*K*N. Bytes: both operands as 16-bit codes (8-bit for
  W8A8) and the float32 product.

The linears of a step are the architecture's: the configuration's program
adapter (`bench/programs/<program>.py`) lists them with `linear_shapes`.
The FPS and lattice counts read PointNet++'s SA stages and serve only the
cells that run those kernels.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"
QUANT_BITS = {"none": None, "sc_w16a16": 16, "sc_w8a8": 8}


@dataclasses.dataclass(frozen=True)
class Call:
    """One kernel call's work: operations, bytes and the compute peak's key."""

    ops: float
    bytes: float
    compute: str = "bf16_flops_per_s"

    def least_s(self, peaks: dict) -> tuple[float, str]:
        """(least seconds, bound name) of this call on a chip with `peaks`."""
        t_ops = self.ops / peaks[self.compute]
        t_mem = self.bytes / peaks["hbm_bytes_per_s"]
        return (t_ops, self.compute) if t_ops >= t_mem else (t_mem, "hbm_bytes_per_s")


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip kind; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def _depth(n: int, m: int, cap: int) -> int:
    while cap > 0 and (n >> cap) < 4 * max(1, m >> cap):
        cap -= 1
    while cap > 0 and (n % (1 << cap) or m % (1 << cap)):
        cap -= 1
    return cap


def sa_tiles(model: dict) -> list[tuple[int, int, int, int]]:
    """(tiles per cloud, points per tile, samples per tile, nsample) per SA stage."""
    out, n = [], model["n_points"]
    for sa in model["sa"]:
        d = _depth(n, sa["n_centroids"], model["msp_depth"])
        out.append((1 << d, n >> d, sa["n_centroids"] >> d, sa["nsample"]))
        n = sa["n_centroids"]
    return out


def fps_calls(model: dict, batch: int) -> list[Call]:
    """One call per SA stage over batch x tiles."""
    return [
        Call(ops=batch * t * (k - 1) * 10 * p, bytes=batch * t * (12 * p + 4 * k))
        for t, p, k, _ in sa_tiles(model)
    ]


def lattice_calls(model: dict, batch: int) -> list[Call]:
    """One call per SA stage over batch x tiles."""
    return [
        Call(ops=batch * t * k * p * 9, bytes=batch * t * (12 * p + 12 * k + 5 * k * s))
        for t, p, k, s in sa_tiles(model)
    ]


def model_flops_per_cloud(linears: list[tuple[int, int, int]]) -> float:
    """2 * rows * fan-in * fan-out summed over every linear of one cloud.

    `linears` is the program adapter's `linear_shapes(model)`.
    """
    return float(sum(2 * r * a * b for r, a, b in linears))


def sc_matmul_calls(linears: list[tuple[int, int, int]], batch: int, quant: str) -> list[Call]:
    """One call per linear of `linears` under a quantized policy; none in float mode."""
    bits = QUANT_BITS[quant]
    if bits is None:
        return []
    byte = bits // 8
    return [
        Call(
            ops=2 * batch * r * a * b,
            bytes=byte * (batch * r * a + a * b) + 4 * batch * r * b,
            compute="int8_ops_per_s",
        )
        for r, a, b in linears
    ]


def roofline_pct(calls: list[Call], events_s: list[float], peaks: dict) -> tuple[float, str] | None:
    """Least time of the traced calls over their device time, in percent.

    `events_s` are the durations of the kernel's trace events; every
    `len(calls)` of them are one batch's calls. Returns (percent, the bound
    of most of the least time) or None where nothing was traced.
    """
    if not calls or not events_s:
        return None
    least = [c.least_s(peaks) for c in calls]
    per_batch = sum(t for t, _ in least)
    bounds: dict[str, float] = {}
    for t, b in least:
        bounds[b] = bounds.get(b, 0.0) + t
    batches = len(events_s) / len(calls)
    return 100.0 * batches * per_batch / sum(events_s), max(bounds, key=bounds.get)
