"""Samplers, zip/memmap caches, predict/export/evaluate CLIs."""

import os
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from deeplearning_tpu.data.samplers import (aspect_ratio_groups,
                                            grouped_batches,
                                            infinite_indices, pk_batches)
from deeplearning_tpu.data.zip_cache import MemmapCache, ZipImageSource

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


class TestSamplers:
    def test_pk_batches_structure(self):
        labels = np.repeat(np.arange(8), 6)       # 8 ids × 6 samples
        batches = pk_batches(labels, p=4, k=3, seed=0)
        assert batches.shape == (2, 12)
        for batch in batches:
            ids = labels[batch]
            uniq, counts = np.unique(ids, return_counts=True)
            assert len(uniq) == 4 and (counts == 3).all()

    def test_pk_with_scarce_identities(self):
        labels = np.asarray([0, 0, 1, 2, 2, 2])
        batches = pk_batches(labels, p=2, k=4, seed=0)
        assert batches.shape[1] == 8             # replacement fills K

    def test_aspect_ratio_grouping(self):
        ars = [0.5, 0.6, 0.55, 1.8, 2.0, 1.9, 0.52, 1.85]
        groups = aspect_ratio_groups(ars, n_groups=2)
        assert set(groups) == {0, 1}
        # wide and tall images land in different groups
        assert groups[0] == groups[1] == groups[2]
        assert groups[3] == groups[4] == groups[5]
        assert groups[0] != groups[3]
        batches = grouped_batches(ars, batch_size=2, seed=0)
        for b in batches:
            assert groups[b[0]] == groups[b[1]]

    def test_infinite_indices_cover_dataset(self):
        it = infinite_indices(5, seed=0)
        first_epoch = [next(it) for _ in range(5)]
        assert sorted(first_epoch) == list(range(5))
        assert isinstance(next(it), (int, np.integer))


class TestZipCache:
    def test_zip_source_roundtrip(self, tmp_path):
        zp = str(tmp_path / "imgs.zip")
        arr = (np.arange(48).reshape(4, 4, 3) % 255).astype(np.uint8)
        with zipfile.ZipFile(zp, "w") as z:
            import io
            buf = io.BytesIO()
            np.save(buf, arr)
            z.writestr("a.npy", buf.getvalue())
            buf2 = io.BytesIO()
            np.save(buf2, arr + 1)
            z.writestr("b.npy", buf2.getvalue())
        src = ZipImageSource(zp)
        assert len(src) == 2
        np.testing.assert_array_equal(src.read_image(0), arr)
        np.testing.assert_array_equal(src.read_image(1), arr + 1)

    def test_memmap_cache_decode_once(self, tmp_path):
        calls = []

        def produce(i):
            calls.append(i)
            return np.full((2, 2), i, np.uint8)

        cache = MemmapCache(str(tmp_path / "c.mm"), (3, 2, 2))
        np.testing.assert_array_equal(cache.get(1, produce),
                                      np.full((2, 2), 1))
        np.testing.assert_array_equal(cache.get(1, produce),
                                      np.full((2, 2), 1))
        assert calls == [1]                       # second get was cached
        assert cache.fill_fraction == pytest.approx(1 / 3)
        # a new handle over the same file sees the fill
        cache2 = MemmapCache(str(tmp_path / "c.mm"), (3, 2, 2))
        assert cache2.fill_fraction == pytest.approx(1 / 3)


class TestToolCLIs:
    def test_predict_cli(self, tmp_path):
        img = (np.random.default_rng(0).uniform(0, 255, (32, 32, 3))
               ).astype(np.float32)
        np.save(tmp_path / "img.npy", img)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "predict.py"),
             "--model", "mnist_cnn", "--num-classes", "4",
             "--input", str(tmp_path / "img.npy"), "--size", "28",
             "--topk", "2"],
            capture_output=True, text=True, timeout=300, env=ENV)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "image 0:" in out.stdout

    def test_export_cli_stablehlo(self, tmp_path):
        out_path = str(tmp_path / "m.shlo")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "export.py"),
             "--model", "mnist_fcn", "--num-classes", "3",
             "--size", "16", "--channels", "1",
             "--format", "stablehlo", "--out", out_path],
            capture_output=True, text=True, timeout=300, env=ENV)
        assert out.returncode == 0, out.stderr[-2000:]
        assert os.path.getsize(out_path) > 0
        assert "FLOPs" in out.stdout

    def test_evaluate_cli(self, tmp_path):
        rng = np.random.default_rng(0)
        labels = rng.integers(0, 3, 64).astype(np.int32)
        images = rng.normal(0, 0.1, (64, 16, 16, 1)).astype(np.float32)
        np.savez(tmp_path / "d.npz", images=images, labels=labels)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "evaluate.py"),
             "--model", "mnist_fcn", "--num-classes", "3",
             "--npz", str(tmp_path / "d.npz"), "--batch", "32"],
            capture_output=True, text=True, timeout=300, env=ENV)
        assert out.returncode == 0, out.stderr[-2000:]
        assert '"top1"' in out.stdout and '"per_class_acc"' in out.stdout
