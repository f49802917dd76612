"""The PointNet++ program adapter: its configuration, its linears and FLOP counts."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, work  # noqa: E402

PN2 = cell.load_program("pointnet2")

# 256 points; SA1 64 centroids, SA2 16 (the smoke shapes of test_bench_work.py)
SMOKE_CLS = {
    "task": "cls", "n_points": 256, "n_classes": 8, "msp_depth": 2,
    "sa": [{"n_centroids": 64, "radius": 0.3, "nsample": 16, "mlp": [32, 32, 64]},
           {"n_centroids": 16, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]}],
    "global_mlp": [128, 256], "head": [128],
}
SMOKE_SEG = dict(SMOKE_CLS, task="seg", fp_mlp=[64, 64], head=[64])

# the published configurations: FLOPs per cloud and linears, as the whole
# step's share (`step_mfu`) and `sc_matmul_roofline` count them
PUBLISHED = {"pointnet2-cls-w16a16": (279_138_304, 12), "pointnet2-seg-fp32": (1_100_382_208, 22)}


def test_linear_shapes_and_model_flops_cls():
    """Every cls linear's rows and widths, and their FLOPs."""
    shapes = PN2.linear_shapes(SMOKE_CLS)
    assert shapes == [
        (256, 3, 32), (256, 32, 32), (256, 32, 64),
        (64, 67, 64), (64, 64, 64), (64, 64, 128),
        (16, 131, 128), (16, 128, 256),
        (1, 256, 128), (1, 128, 8),
    ]
    assert work.model_flops_per_cloud(shapes) == sum(2 * r * a * b for r, a, b in shapes)


def test_linear_shapes_seg():
    """Every seg linear's rows and widths."""
    assert PN2.linear_shapes(SMOKE_SEG) == [
        (256, 3, 32), (256, 32, 32), (256, 32, 64),
        (64, 67, 64), (64, 64, 64), (64, 64, 128),
        (64, 192, 64), (64, 64, 64),  # FP onto the 64 SA1 centroids: 128 + 64 skip
        (256, 67, 64), (256, 64, 64),  # FP onto the raw points: 64 + 3 xyz
        (256, 64, 64), (256, 64, 8),
    ]


def test_full_cls_model_flops():
    """Model FLOPs of the full cls configuration by hand."""
    full = dict(SMOKE_CLS, n_points=1024, msp_depth=2,
                sa=[{"n_centroids": 256, "radius": 0.2, "nsample": 32, "mlp": [64, 64, 128]},
                    {"n_centroids": 64, "radius": 0.4, "nsample": 32, "mlp": [128, 128, 256]}],
                global_mlp=[256, 512, 1024], head=[512, 256])
    hand = (2 * 1024 * (3 * 64 + 64 * 64 + 64 * 128) + 2 * 256 * (131 * 128 + 128 * 128 + 128 * 256)
            + 2 * 64 * (259 * 256 + 256 * 512 + 512 * 1024) + 2 * (1024 * 512 + 512 * 256 + 256 * 8))
    assert work.model_flops_per_cloud(PN2.linear_shapes(full)) == hand


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_configuration_flops(name):
    """The benchmark's configurations count the FLOPs and linears they always have."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    linears = cell.load_program(cfg["program"]).linear_shapes(cfg["model"])
    assert (work.model_flops_per_cloud(linears), len(linears)) == PUBLISHED[name]


@pytest.mark.parametrize("model", [SMOKE_CLS, SMOKE_SEG], ids=["cls", "seg"])
def test_input_features_widen_the_point_linears(model):
    """xyz + 3 features: SA1's fan-in and, in seg, the finest FP skip take 3 more."""
    plain = PN2.linear_shapes(model)
    assert PN2.linear_shapes(dict(model, in_features=0)) == plain
    wide = PN2.linear_shapes(dict(model, in_features=3))
    changed = [(i, a, b) for i, (a, b) in enumerate(zip(plain, wide)) if a != b]
    assert changed[0] == (0, (256, 3, 32), (256, 6, 32))
    if model["task"] == "seg":
        assert changed[1:] == [(8, (256, 67, 64), (256, 70, 64))]
    else:
        assert changed[1:] == []


def test_config_is_the_programs_configuration():
    """`config` builds the program's PointNet2Config, input features included."""
    from repro.models.pointnet2 import PointNet2Config, SAConfig

    model = dict(SMOKE_SEG, name="s", preproc="pc2im", aggregation="delayed")
    assert PN2.config(model) == PointNet2Config(
        name="s", task="seg", n_points=256, n_classes=8, in_features=0,
        sa=(SAConfig(64, 0.3, 16, (32, 32, 64)), SAConfig(16, 0.6, 16, (64, 64, 128))),
        global_mlp=(128, 256), fp_mlp=(64, 64), head=(64,), preproc="pc2im",
        aggregation="delayed", msp_depth=2,
    )
    assert PN2.config(dict(model, in_features=3)).in_features == 3


def test_a_missing_program_file_is_an_error_naming_it(tmp_path):
    """A configuration whose adapter file is missing fails with the file's name."""
    with pytest.raises(FileNotFoundError, match="bench/programs/nowhere.py"):
        cell.load_program("nowhere", tmp_path)


def test_benchlib_imports_no_model_of_the_program():
    """Only the adapters import the program's models."""
    for path in (ROOT / "bench" / "benchlib").glob("*.py"):
        assert "repro.models" not in path.read_text(), path.name
