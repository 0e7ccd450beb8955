"""Utils (norms/visualization/profiling) + the unified train CLI."""

import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.utils import normalization as N
from deeplearning_tpu.utils import profiling as P
from deeplearning_tpu.utils import visualize as V

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestNormalizationDemos:
    def _x(self):
        return jnp.asarray(np.random.default_rng(0).normal(
            2.0, 3.0, (4, 8, 8, 6)), jnp.float32)

    def test_batch_norm_matches_flax(self):
        x = self._x()
        ours = N.batch_norm(x, jnp.ones(6), jnp.zeros(6))
        bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                          epsilon=1e-5)
        ref, _ = bn.init_with_output(jax.random.key(0), x)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   atol=1e-4)

    def test_layer_norm_matches_flax(self):
        x = self._x()
        ours = N.layer_norm(x, jnp.ones(6), jnp.zeros(6))
        ref = nn.LayerNorm(epsilon=1e-5).init_with_output(
            jax.random.key(0), x)[0]
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   atol=1e-4)

    def test_group_norm_matches_flax(self):
        x = self._x()
        ours = N.group_norm(x, jnp.ones(6), jnp.zeros(6), groups=3)
        ref = nn.GroupNorm(num_groups=3, epsilon=1e-5).init_with_output(
            jax.random.key(0), x)[0]
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   atol=1e-4)

    def test_instance_norm_reduces_hw(self):
        x = self._x()
        out = N.instance_norm(x, jnp.ones(6), jnp.zeros(6))
        m = np.asarray(out).mean(axis=(1, 2))
        np.testing.assert_allclose(m, 0.0, atol=1e-4)


class TestVisualize:
    def test_feature_map_grid(self):
        f = np.random.default_rng(0).normal(size=(8, 8, 5))
        img = V.feature_map_grid(f)
        assert img.dtype == np.uint8
        assert img.ndim == 2 and img.shape[0] >= 8

    def test_kernel_grid(self):
        k = np.random.default_rng(0).normal(size=(3, 3, 4, 10))
        img = V.kernel_grid(k)
        assert img.dtype == np.uint8

    def test_capture_feature_maps(self):
        from deeplearning_tpu.core.registry import MODELS
        model = MODELS.build("mnist_cnn", num_classes=3, dtype=jnp.float32)
        x = jnp.zeros((1, 28, 28, 1))
        variables = model.init(jax.random.key(0), x, train=False)
        feats = V.capture_feature_maps(model, variables, x)
        assert feats                      # at least one intermediate
        assert any(v.ndim == 4 for v in feats.values())

    def test_draw_boxes(self):
        img = np.zeros((32, 32, 3), np.uint8)
        out = V.draw_boxes(img, np.asarray([[4, 4, 20, 20]]))
        assert (out[4, 4:20] == (0, 255, 0)).all()
        assert (out[10, 10] == (0, 0, 0)).all()   # interior untouched


class TestProfiling:
    def test_compiled_flops_and_mfu_refuses_unknown_device(self):
        f = jax.jit(lambda x: x @ jnp.ones((16, 16)))
        x = jnp.ones((8, 16))
        flops = P.compiled_flops(f, x)
        assert flops > 0
        # the CPU has no entry in the peak table: an "MFU" against some
        # other chip's peak is refused, not defaulted
        with pytest.raises(ValueError, match="no bf16 peak"):
            P.device_peak_flops()


class TestTrainCLI:
    def test_end_to_end_cli(self, tmp_path):
        # the suite's XLA_FLAGS (conftest.py): eight devices, quick compiles
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "train.py"),
             "--cfg", os.path.join(REPO, "configs", "mnist_smoke.yaml"),
             "train.epochs=1", "data.n_train=128",
             f"train.workdir={tmp_path}/run"],
            capture_output=True, text=True, timeout=600, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "top1" in out.stdout
        assert os.path.isdir(f"{tmp_path}/run/ckpt")

    def test_base_yaml_inheritance(self):
        from deeplearning_tpu.core.config import load_config
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from train import Config
        cfg = load_config(Config(),
                          os.path.join(REPO, "configs",
                                       "resnet50_base.yaml"))
        assert cfg.model.name == "resnet50"      # child override
        assert cfg.data.global_batch == 64       # inherited from base
        assert cfg.data.channels == 3


class TestNativeSavedModelRunner:
    def test_cpp_runner_matches_python(self, tmp_path):
        import subprocess
        import tempfile
        try:
            import tensorflow  # noqa: F401
        except ImportError:
            pytest.skip("tensorflow unavailable")
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from build_savedmodel_runner import build
        try:
            binary = build()
        except Exception:
            pytest.skip("no toolchain for the TF C API runner")
        from deeplearning_tpu.core.registry import MODELS
        from deeplearning_tpu.export.serialize import export_savedmodel
        model = MODELS.build("mnist_fcn", num_classes=3, dtype=jnp.float32)
        x = jnp.zeros((1, 8, 8, 1))
        variables = model.init(jax.random.key(0), x, train=False)

        def fn(img):
            return model.apply(variables, img, train=False)
        d = str(tmp_path / "sm")
        if not export_savedmodel(fn, [x], d):
            pytest.skip("savedmodel export unavailable")
        ramp = (0.001 * (np.arange(64) % 1000)).astype(
            np.float32).reshape(1, 8, 8, 1)
        expected = np.asarray(fn(jnp.asarray(ramp))).reshape(-1)
        out = subprocess.run(
            [binary, d, "serving_default_arg0:0",
             "StatefulPartitionedCall:0", "1,8,8,1"],
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-1500:]
        vals = [float(v) for v in out.stdout.split("values:")[1].split()]
        np.testing.assert_allclose(vals, expected[:len(vals)], atol=1e-4)
