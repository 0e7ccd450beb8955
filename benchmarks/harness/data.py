"""The one generator of training traffic: an image set made from the seed,
as the parameters of a traffic file say, written where the program's own
``data.npz`` path reads it. uint8 images, so the program's feed converts and
scales every sample, as it does for real data."""

from __future__ import annotations

import glob
import os

import numpy as np


def image_set(seed: int, n: int, size: int, classes: int):
    """(n, size, size, 3) uint8 of uniform noise and (n,) int32 labels. Every
    row differs from every other (its first bytes are checked to be unique)."""
    rng = np.random.default_rng([seed, n, size])
    images = np.frombuffer(rng.bytes(n * size * size * 3), np.uint8).reshape(
        n, size, size, 3)
    labels = rng.integers(0, classes, n).astype(np.int32)
    if len({row_key(img) for img in images}) != n:
        raise ValueError("two generated images share their first bytes")
    return images, labels


def row_key(image_u8: np.ndarray) -> bytes:
    return image_u8.reshape(-1)[:16].tobytes()


def write_npz(directory: str, stem: str, seed: int, images, labels) -> str:
    """Keep one seed's set on disk at a time: the driver draws new seeds for
    every check, and a set is 0.6 GB."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{stem}_{seed}.npz")
    for old in glob.glob(os.path.join(directory, f"{stem}_*.npz")):
        if old != path:
            os.remove(old)
    if not os.path.exists(path):
        tmp = path + ".tmp.npz"
        np.savez(tmp, images=images, labels=labels)
        os.replace(tmp, path)
    return path


def row_index(images: np.ndarray) -> dict:
    return {row_key(img): i for i, img in enumerate(images)}


def match_rows(batch_images: np.ndarray, batch_labels: np.ndarray,
               images: np.ndarray, labels: np.ndarray, index: dict) -> dict:
    """Which rows of the set the feed delivered, judged by content: every
    delivered row has to be one image of the set, scaled to [0, 1] exactly as
    uint8 / 255 in float32, with that image's label, and no row twice. Hands
    back those rows as the reference's batch, made from the set."""
    heads = batch_images.reshape(len(batch_images), -1)[:, :16]
    ids = [index.get(np.rint(h * 255.0).astype(np.uint8).tobytes(), -1)
           for h in heads]
    wrong = sum(i < 0 for i in ids)
    good = [i for i in ids if i >= 0]
    want = None
    if not wrong:
        want = images[ids].astype(np.float32) / np.float32(255.0)
        wrong = int(np.sum(np.any((want != batch_images).reshape(len(ids), -1),
                                  axis=1)))
        wrong += int(np.sum(labels[ids] != batch_labels))
    return {"ids": ids, "wrong": wrong + (len(good) - len(set(good))),
            "images": want, "labels": labels[ids] if not wrong else None}
