"""Work functions and rooflines against hand counts at smoke shapes."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, work  # noqa: E402

PN2 = cell.load_program("pointnet2")

PEAKS = {"bf16_flops_per_s": 200e12, "int8_ops_per_s": 400e12, "hbm_bytes_per_s": 800e9}

# 256 points; SA1 64 centroids / 16 neighbours (depth 2: 4 tiles of 64
# points, 16 samples each); SA2 16 of 64 (depth 2: 4 tiles of 16 points,
# 4 samples each, since 64 >> 2 = 16 is not below 4 * 4)
SMOKE_CLS = {
    "task": "cls", "n_points": 256, "n_classes": 8, "msp_depth": 2,
    "sa": [{"n_centroids": 64, "radius": 0.3, "nsample": 16, "mlp": [32, 32, 64]},
           {"n_centroids": 16, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]}],
    "global_mlp": [128, 256], "head": [128],
}


def test_sa_tiles_hand_count():
    """Tiles, points, samples and neighbours per SA stage."""
    assert work.sa_tiles(SMOKE_CLS) == [(4, 64, 16, 16), (4, 16, 4, 16)]


def test_fps_and_lattice_hand_count():
    """FPS and lattice operations and bytes by hand."""
    fps = work.fps_calls(SMOKE_CLS, batch=2)
    assert fps[0].ops == 2 * 4 * 15 * 10 * 64
    assert fps[0].bytes == 2 * 4 * (12 * 64 + 4 * 16)
    lat = work.lattice_calls(SMOKE_CLS, batch=2)
    assert lat[1].ops == 2 * 4 * 4 * 16 * 9
    assert lat[1].bytes == 2 * 4 * (12 * 16 + 12 * 4 + 5 * 4 * 16)


def test_sc_matmul_calls():
    """SC matmul calls of the adapter's linears: none in float mode, 2*M*K*N against the int8 peak."""
    linears = PN2.linear_shapes(SMOKE_CLS)
    assert work.sc_matmul_calls(linears, 8, "none") == []
    calls = work.sc_matmul_calls(linears, 8, "sc_w16a16")
    assert calls[0].ops == 2 * 8 * 256 * 3 * 32
    assert calls[0].bytes == 2 * (8 * 256 * 3 + 3 * 32) + 4 * 8 * 256 * 32
    assert calls[0].compute == "int8_ops_per_s"
    assert work.sc_matmul_calls(linears, 8, "sc_w8a8")[0].bytes == (
        8 * 256 * 3 + 3 * 32 + 4 * 8 * 256 * 32)


@pytest.mark.parametrize("kind", ["fps", "lattice", "sc_matmul"])
def test_roofline_names_its_bound_and_reads_100_at_the_least_time(kind):
    """Rooflines name their bound and read 100% at the least time."""
    calls = {"fps": work.fps_calls(SMOKE_CLS, 8),
             "lattice": work.lattice_calls(SMOKE_CLS, 8),
             "sc_matmul": work.sc_matmul_calls(PN2.linear_shapes(SMOKE_CLS), 8, "sc_w16a16")}[kind]
    least = [c.least_s(PEAKS) for c in calls]
    assert all(b in ("bf16_flops_per_s", "int8_ops_per_s", "hbm_bytes_per_s") for _, b in least)
    events = [t for t, _ in least] * 3  # three batches, each call at its least time
    pct, bound = work.roofline_pct(calls, events, PEAKS)
    assert pct == pytest.approx(100.0)
    assert bound in {b for _, b in least}
    assert work.roofline_pct(calls, [2 * t for t in events], PEAKS)[0] == pytest.approx(50.0)
    assert work.roofline_pct(calls, [], PEAKS) is None


def test_least_time_bound_choice():
    """The least time picks the slower of compute and memory."""
    c = work.Call(ops=2e12, bytes=1.0)
    assert c.least_s(PEAKS) == (0.01, "bf16_flops_per_s")
    c = work.Call(ops=1.0, bytes=8e9)
    assert c.least_s(PEAKS) == (0.01, "hbm_bytes_per_s")


def test_peaks_table_keyed_by_device_kind():
    """Peaks are keyed by device kind; an unknown kind is an error."""
    p = work.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
