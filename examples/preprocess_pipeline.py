"""PC2IM preprocessing anatomy: partition -> FPS -> lattice query, with the
Pallas kernels (interpret mode on CPU), the batched PreprocessEngine, and
the utilisation/energy story.

    PYTHONPATH=src python examples/preprocess_pipeline.py"""

import jax
import jax.numpy as jnp

from repro.core import energy as E
from repro.core import fps as F
from repro.core import partition as P
from repro.core.engine import EngineConfig, PreprocessEngine
from repro.core.preprocess import preprocess_pc2im
from repro.data.pointclouds import sample_batch
from repro.kernels import registry
from repro.kernels.fps.ops import fps_tiles
from repro.kernels.lattice.ops import lattice_query_fused

batch, _, _ = sample_batch(jax.random.PRNGKey(0), 4, 2048)
pts = batch[0]

# --- C2: median spatial partitioning vs fixed-grid tiles --------------------
msp = P.median_partition(pts, depth=3)
grid = P.grid_partition(pts, grid=2, capacity=512)
print(f"MSP   : {msp.n_tiles} tiles x {msp.tile_size} pts, utilisation {float(msp.utilization()):.2f}")
print(f"grid  : {grid.n_tiles} tiles x {grid.tile_size} cap, utilisation {float(grid.utilization()):.2f}"
      f"  <- the padding waste MSP removes (paper: +15%)")

# --- C1+C3: in-VMEM tiled L1 FPS (the APD-CIM/Ping-Pong-MAX kernel) ---------
tiled = jnp.take(pts, msp.tiles, axis=0)  # (8, 256, 3) zero padding
idx_kernel = fps_tiles(tiled, 64, metric="l1", backend="pallas")
idx_xla = fps_tiles(tiled, 64, metric="l1", backend="xla")
print(f"tiled FPS kernel == oracle: {bool((idx_kernel == idx_xla).all())}")

# --- C1: fused lattice query -------------------------------------------------
centroids = jnp.take(pts, jnp.take(msp.tiles[0], idx_kernel[0]), axis=0)
nbrs = lattice_query_fused(pts, centroids, radius=0.3, nsample=16,
                           backend="pallas")
print(f"lattice query: fill-rate {float(nbrs.mask.mean()):.2f} (L = 1.6R)")

# --- the batched PreprocessEngine (B clouds -> ONE kernel grid) --------------
engine = PreprocessEngine(EngineConfig(
    pipeline="pc2im", n_centroids=512, radius=0.3, nsample=16, depth=3))
res = engine(batch)  # (4, 2048, 3) -> centroid_idx (4, 512), neighbors (4, 512, 16)
per_cloud = preprocess_pc2im(batch[0], 512, 0.3, 16, depth=3)
print(f"engine: {batch.shape[0]} clouds x {res.centroid_idx.shape[1]} centroids in one "
      f"launch ({registry.names()} registered); "
      f"batched == per-cloud: {bool((res.centroid_idx[0] == per_cloud.centroid_idx).all())}")

# --- quality: L1 sampling vs exact L2 ----------------------------------------
i2 = F.fps(pts, 256, metric="l2")
i1 = F.fps(pts, 256, metric="l1")
print(f"coverage radius L1/L2: "
      f"{float(F.coverage_radius(pts, i1)/F.coverage_radius(pts, i2)):.3f} (paper: ~1, Fig 5a)")

# --- the memory-traffic ledger (Challenge I) ---------------------------------
w = E.WORKLOADS["semantickitti_16k"]
b2 = E.preproc_energy_baseline2(w)
print("\nTiPU-style tiled FPS energy split (paper: 41% points / 58% TDs):")
tot = b2["fps_point"] + b2["fps_td"]
print(f"  point reads {b2['fps_point']/tot*100:.0f}%  TD update {b2['fps_td']/tot*100:.0f}%")
