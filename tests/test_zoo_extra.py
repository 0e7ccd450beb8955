"""Happy-Whale modelZoo backbones + staged mask-crop pipeline.

Covers models/classification/zoo_extra.py (modelZoo/{dpn, inceptionV4,
nasnet, ployNet, senet, xception}.py surface) and models/metric/
mask_crop.py (fcn_mask/predict.py + retrieval data_loader crop surface).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.models.metric.mask_crop import (
    crop_by_mask, make_mask_predictor, mask_crop_source, mask_to_bbox,
    write_masks)

SMALL = {  # shrunk configs so CPU forward+init stays fast
    "xception": {},
    "inception_v4": {"blocks": (1, 1, 1)},
    "dpn68": {"k_sec": (1, 1, 1, 1)},
    "dpn92": {"k_sec": (1, 1, 1, 1)},
    "nasnet_a_mobile": {"n_normal": 1},
    "polynet": {"stage_blocks": (3, 3, 3)},
    "senet154": {"blocks": (1, 1, 1, 1)},
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_zoo_backbone_forward(name):
    m = MODELS.build(name, num_classes=7, **SMALL[name])
    # init at the forward's batch: the eager forward then reuses every op
    # init compiled (the weights do not depend on the batch)
    x = jnp.zeros((2, 96, 96, 3))
    v = m.init(jax.random.key(0), x, train=False)
    out = m.apply(v, x, train=False)
    assert out.shape == (2, 7)
    assert out.dtype == jnp.float32
    # train mode mutates BN stats
    out2, mut = m.apply(v, jnp.ones((2, 96, 96, 3)), train=True,
                        mutable=["batch_stats"])
    assert out2.shape == (2, 7) and "batch_stats" in mut


def test_mask_to_bbox_and_crop():
    mask = np.zeros((64, 64), np.float32)
    mask[10:30, 20:50] = 1.0
    x0, y0, x1, y1 = mask_to_bbox(mask, pad_frac=0.0)
    assert (x0, y0, x1, y1) == (20, 10, 50, 30)
    # padding stays inside the image
    x0, y0, x1, y1 = mask_to_bbox(mask, pad_frac=0.5)
    assert x0 >= 0 and y0 >= 0 and x1 <= 64 and y1 <= 64
    # empty mask → whole image
    assert mask_to_bbox(np.zeros((32, 48))) == (0, 0, 48, 32)
    img = np.random.default_rng(0).normal(size=(64, 64, 3)).astype(
        np.float32)
    crop = crop_by_mask(img, mask, out_hw=(24, 24), pad_frac=0.0)
    assert crop.shape == (24, 24, 3)


def test_crop_by_mask_resolution_mismatch():
    """Stage-1 masks are predicted at a fixed size; the bbox must be
    rescaled into image space, not applied in mask coordinates."""
    img = np.zeros((128, 256, 3), np.float32)
    img[64:96, 128:192] = 7.0          # object in image space
    mask = np.zeros((64, 64), np.float32)
    mask[32:48, 32:48] = 1.0           # same object in 64x64 mask space
    crop = crop_by_mask(img, mask, pad_frac=0.0)
    assert crop.shape == (32, 64, 3)   # 16/64 of 128, 16/64 of 256
    assert (crop == 7.0).all()
    # empty mask falls back to the FULL image, not the mask extent
    full = crop_by_mask(img, np.zeros((64, 64)), pad_frac=0.0)
    assert full.shape == img.shape


def test_staged_mask_crop_pipeline(tmp_path):
    """Stage 1 writes masks from a (random-weight) U-Net head; stage 2's
    source crops by them; the retrieval model embeds the crops."""
    imgs_dir = tmp_path / "imgs"
    imgs_dir.mkdir()
    from PIL import Image
    rng = np.random.default_rng(0)
    paths, labels = [], []
    for i in range(4):
        arr = rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
        arr[16:48, 16:48] = 255  # bright square the mask should find
        p = imgs_dir / f"w{i}.jpg"
        Image.fromarray(arr).save(p)
        paths.append(str(p))
        labels.append(i % 2)

    seg = MODELS.build("unet", num_classes=1, base_features=8)
    v = seg.init(jax.random.key(0), jnp.zeros((2, 64, 64, 3)),
                 train=False)
    predictor = make_mask_predictor(seg, v)
    n = write_masks(predictor, paths, str(tmp_path / "masks"),
                    image_size=(64, 64), batch=2)
    assert n == 4
    src = mask_crop_source(paths, labels, str(tmp_path / "masks"),
                           out_hw=(32, 32))
    sample = src[0]
    assert sample["image"].shape == (32, 32, 3)

    retr = MODELS.build("arcface_resnet18", num_classes=2)
    rv = retr.init(jax.random.key(1), jnp.zeros((4, 32, 32, 3)),
                   train=False)
    batch = np.stack([src[i]["image"] for i in range(4)])
    out = retr.apply(rv, jnp.asarray(batch), train=False,
                     mutable=["batch_stats"])[0]
    emb = out["embedding"] if isinstance(out, dict) else out
    assert np.all(np.isfinite(np.asarray(emb, np.float32)))
