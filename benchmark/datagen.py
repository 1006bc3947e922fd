"""Image data from ``--seed``: the content class of the old bench
(cell blobs + sensor noise, copied from ``flagship.synthetic_wsi_tiles``
so the yardstick keeps its own generator), assembled into planes in this
process and written through the program's own ingest (``build_pyramid``:
the store format is the system under test's).

The level-0 arrays stay in this process: they are what the plain
reference renders from after the window has closed.
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import time

import numpy as np


def synthetic_tiles(rng: np.random.Generator, B: int, C: int, H: int,
                    W: int, blobs: int = 12) -> np.ndarray:
    """``B`` microscopy-like uint16 tiles [B, C, H, W]: gaussian blobs
    (separable outer products) over a dim background with read noise."""
    cy = rng.uniform(0, H, size=(B, C, blobs, 1))
    cx = rng.uniform(0, W, size=(B, C, blobs, 1))
    s = rng.uniform(H / 40, H / 8, size=(B, C, blobs, 1))
    amp = rng.uniform(5_000, 35_000, size=(B, C, blobs))
    ys = np.exp(-((np.arange(H)[None, None, None, :] - cy) ** 2)
                / (2 * s * s)).astype(np.float32)
    xs = np.exp(-((np.arange(W)[None, None, None, :] - cx) ** 2)
                / (2 * s * s)).astype(np.float32)
    img = np.einsum("bcky,bckx,bck->bcyx", ys, xs,
                    amp.astype(np.float32), optimize=True)
    img += 200.0 + rng.normal(0, 300.0, size=img.shape)
    return np.clip(img, 0, 65535).astype(np.uint16)


def extent(config: dict) -> tuple:
    """(images, tiles_x, tiles_y): the configuration's scale, as its
    file states it and as every cell on it runs it."""
    tx, ty = config["level0_tiles"]
    return int(config["images"]), int(tx), int(ty)


def generate(config: dict, seed: int, data_dir: str) -> dict:
    """Write every image of the deployment under ``data_dir/<id>`` and
    return ``{image_id: uint16[C, H, W]}`` (level 0)."""
    from omero_ms_image_region_tpu.io.store import build_pyramid

    t0 = time.perf_counter()
    n_images, tx, ty = extent(config)
    C, edge = int(config["channels"]), int(config["content_edge"])
    chunk = int(config["store_chunk"])
    images = {i + 1: np.empty((C, ty * edge, tx * edge), np.uint16)
              for i in range(n_images)}
    seeds = np.random.SeedSequence(int(seed)).spawn(n_images * ty)

    def tile_row(job: int) -> None:
        image, y = divmod(job, ty)
        tiles = synthetic_tiles(np.random.default_rng(seeds[job]),
                                tx, C, edge, edge)
        img = images[image + 1]
        for x in range(tx):
            img[:, y * edge:(y + 1) * edge,
                x * edge:(x + 1) * edge] = tiles[x]

    workers = min(8, os.cpu_count() or 2)
    with cf.ThreadPoolExecutor(workers) as pool:
        list(pool.map(tile_row, range(n_images * ty)))
    t_gen = time.perf_counter() - t0

    def ingest(image_id: int) -> None:
        build_pyramid(
            images[image_id][:, None],
            os.path.join(data_dir, str(image_id)), chunk=(chunk, chunk),
            n_levels=None if config["pyramid"] else 1,
            min_level_size=min(256, edge)).close()

    with cf.ThreadPoolExecutor(workers if n_images > 1 else 1) as pool:
        list(pool.map(ingest, images))
    total = sum(a.nbytes for a in images.values())
    return {"images": images, "gen_s": t_gen,
            "ingest_s": time.perf_counter() - t0 - t_gen,
            "level0_bytes": total}
