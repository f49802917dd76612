"""Program adapter: PointNet++ as the program serves it (`repro.models.pointnet2`).

A configuration names its adapter with "program"; the harness loads
`bench/programs/<program>.py` by its path and reads only these two
functions, so a configuration of another architecture brings an adapter of
its own and edits nothing here.

* `config(model)`: the program's model configuration, which
  `ServingRuntime` and `get_accelerator` take.
* `linear_shapes(model)`: (rows per cloud, fan-in, fan-out) of every
  linear, the shapes behind `work.model_flops_per_cloud` (`step_mfu`) and
  `work.sc_matmul_calls` (`sc_matmul_roofline`).

`model` is the configuration file's "model" group. "in_features" (default
0) is the per-point channels beyond xyz that each cloud carries.
"""

from __future__ import annotations


def config(model: dict):
    """The `PointNet2Config` of the configuration's model group."""
    from repro.models.pointnet2 import PointNet2Config, SAConfig

    return PointNet2Config(
        name=model["name"], task=model["task"], n_points=model["n_points"],
        n_classes=model["n_classes"], in_features=model.get("in_features", 0),
        sa=tuple(SAConfig(s["n_centroids"], s["radius"], s["nsample"], tuple(s["mlp"]))
                 for s in model["sa"]),
        global_mlp=tuple(model.get("global_mlp", ())),
        fp_mlp=tuple(model.get("fp_mlp", ())),
        head=tuple(model["head"]), preproc=model["preproc"],
        aggregation=model["aggregation"], msp_depth=model["msp_depth"],
    )


def linear_shapes(model: dict) -> list[tuple[int, int, int]]:
    """(rows per cloud, fan-in, fan-out) of every linear, in forward order.

    SA stages run their MLP on every point of their level (delayed
    aggregation); cls ends in the global MLP on the last level and the head
    on one row; seg walks the FP stages back to the raw points, where the
    skip input is xyz and the input features.
    """
    point_c = 3 + model.get("in_features", 0)
    shapes, rows, c_in = [], model["n_points"], point_c
    chans_per_rows = []
    for sa in model["sa"]:
        chans_per_rows.append((rows, [c_in] + list(sa["mlp"])))
        rows, c_in = sa["n_centroids"], sa["mlp"][-1] + 3
    if model["task"] == "cls":
        chans_per_rows.append((rows, [c_in] + list(model["global_mlp"])))
        head = [model["global_mlp"][-1]] + list(model["head"]) + [model["n_classes"]]
        chans_per_rows.append((1, head))
    else:
        level_rows = [model["n_points"]] + [sa["n_centroids"] for sa in model["sa"]]
        skips = [point_c] + [sa["mlp"][-1] for sa in model["sa"][:-1]]
        c_coarse, fp = model["sa"][-1]["mlp"][-1], model["fp_mlp"]
        for i, skip_c in enumerate(reversed(skips)):
            cout = fp[min(i, len(fp) - 1)]
            chans_per_rows.append((level_rows[-2 - i], [c_coarse + skip_c, cout, cout]))
            c_coarse = cout
        head = [c_coarse] + list(model["head"]) + [model["n_classes"]]
        chans_per_rows.append((model["n_points"], head))
    for r, chans in chans_per_rows:
        shapes.extend((r, a, b) for a, b in zip(chans[:-1], chans[1:]))
    return shapes
