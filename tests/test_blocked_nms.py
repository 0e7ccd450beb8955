"""Blocked bitmask NMS + one-pass RoIAlign: equivalence, memory and
wall-clock acceptance.

The contract under test (ISSUE 3 tentpole): the blocked lax sweep and
the Pallas tile kernel produce the *same keep set in the same order* as
the greedy reference across randomized cases, never materialize an N×N
IoU buffer, and beat the reference by >= 3x wall-clock at N=20k on CPU;
one-pass multiscale RoIAlign matches the masked reference bitwise-close
while doing a single bilinear sampling pass.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.analysis import jaxpr as ana_jaxpr
from deeplearning_tpu.ops import nms as nms_ops
from deeplearning_tpu.ops import roi_align as roi_ops
from deeplearning_tpu.ops.pallas import nms as pallas_nms


def make_cases(rng, cases, n, span=64.0, wh_max=24.0, nan_frac=0.0):
    """Overlap-heavy random boxes: (cases, n, 4) + scores (cases, n)."""
    ctr = rng.uniform(0, span, (cases, n, 2))
    wh = rng.uniform(2.0, wh_max, (cases, n, 2))
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2],
                           axis=-1).astype(np.float32)
    scores = rng.uniform(0.0, 1.0, (cases, n)).astype(np.float32)
    if nan_frac:
        mask = rng.uniform(size=scores.shape) < nan_frac
        scores[mask] = np.nan
    return jnp.asarray(boxes), jnp.asarray(scores)


def assert_same_keeps(ref, got, context=""):
    """(idx, valid) pairs agree: same valid mask, same indices on valid
    slots (both paths emit keeps in descending-score order)."""
    i1, v1 = map(np.asarray, ref)
    i2, v2 = map(np.asarray, got)
    assert np.array_equal(v1, v2), f"valid mask mismatch {context}"
    assert np.all((i1 == i2) | ~v1), f"keep indices mismatch {context}"


# The four (iou_thresh, score_thresh, max_out) regimes the property
# tests sweep; with 256 random cases each, every path sees >= 1024
# randomized cases total.
CONFIGS = [
    (0.5, float("-inf"), 64),
    (0.3, 0.25, 32),
    (0.7, 0.5, 16),
    (0.45, 0.05, 100),
]


class TestBlockedEquivalence:
    """Lax blocked sweep == greedy reference, 1024 randomized cases."""

    def test_keep_set_equivalence_1024_cases(self):
        rng = np.random.default_rng(0)
        n = 200                       # pads to 256 at block 64 (nb=4)
        for ci, (th, st, mo) in enumerate(CONFIGS):
            ref = jax.jit(jax.vmap(functools.partial(
                nms_ops.nms_reference, iou_threshold=th, max_out=mo,
                score_threshold=st)))
            blk = jax.jit(jax.vmap(functools.partial(
                nms_ops.nms_blocked, iou_threshold=th, max_out=mo,
                score_threshold=st, block_size=64)))
            boxes, scores = make_cases(rng, 256, n,
                                       nan_frac=0.02 if ci == 0 else 0.0)
            assert_same_keeps(ref(boxes, scores), blk(boxes, scores),
                              context=f"config {ci}")

    def test_class_aware_equivalence(self):
        rng = np.random.default_rng(1)
        boxes, scores = make_cases(rng, 128, 150)
        classes = jnp.asarray(
            rng.integers(0, 5, (128, 150)).astype(np.int32))
        ref = jax.jit(jax.vmap(functools.partial(
            nms_ops.batched_nms, iou_threshold=0.5, max_out=40,
            score_threshold=0.1, impl="greedy")))
        blk = jax.jit(jax.vmap(functools.partial(
            nms_ops.batched_nms, iou_threshold=0.5, max_out=40,
            score_threshold=0.1, impl="blocked", block_size=32)))
        assert_same_keeps(ref(boxes, scores, classes),
                          blk(boxes, scores, classes), "class-aware")

    def test_all_suppressed_single_keep(self):
        # identical boxes: exactly the top-scoring one survives
        boxes = jnp.tile(jnp.asarray([[10., 10., 20., 20.]]), (64, 1))
        scores = jnp.linspace(0.1, 0.9, 64)
        idx, valid = nms_ops.nms_blocked(boxes, scores, 0.5, 10,
                                         block_size=16)
        assert int(valid.sum()) == 1
        assert int(idx[0]) == 63      # highest score
        assert_same_keeps(nms_ops.nms_reference(boxes, scores, 0.5, 10),
                          (idx, valid), "all-suppressed")

    def test_empty_below_threshold(self):
        rng = np.random.default_rng(2)
        boxes, scores = make_cases(rng, 1, 80)
        for fn in (nms_ops.nms_reference, nms_ops.nms_blocked):
            idx, valid = fn(boxes[0], scores[0], 0.5, 20,
                            score_threshold=2.0)   # nothing passes
            assert int(np.asarray(valid).sum()) == 0
            assert np.all(np.asarray(idx) == 0)

    def test_max_out_exceeds_n(self):
        rng = np.random.default_rng(3)
        boxes, scores = make_cases(rng, 1, 7, span=500.0, wh_max=4.0)
        assert_same_keeps(
            nms_ops.nms_reference(boxes[0], scores[0], 0.5, 32),
            nms_ops.nms_blocked(boxes[0], scores[0], 0.5, 32),
            "max_out > n")

    def test_dispatcher_and_default(self):
        rng = np.random.default_rng(4)
        boxes, scores = make_cases(rng, 1, 300)
        ref = nms_ops.nms(boxes[0], scores[0], 0.5, 30, impl="greedy")
        for impl in ("blocked", "pallas", "auto", "reference"):
            assert_same_keeps(ref,
                              nms_ops.nms(boxes[0], scores[0], 0.5, 30,
                                          impl=impl), impl)
        prev = nms_ops.set_default_nms_impl("greedy")
        try:
            assert nms_ops.get_default_nms_impl() == "greedy"
            assert_same_keeps(ref, nms_ops.nms(boxes[0], scores[0],
                                               0.5, 30), "default")
        finally:
            nms_ops.set_default_nms_impl(prev)
        with pytest.raises(ValueError):
            nms_ops.set_default_nms_impl("cuda")


class TestPallasEquivalence:
    """Pallas tile kernel (interpret mode on CPU) == greedy reference,
    1024 randomized cases."""

    def test_keep_set_equivalence_1024_cases(self):
        rng = np.random.default_rng(10)
        n = 200                       # pads to 256 at block 64
        for ci, (th, st, mo) in enumerate(CONFIGS):
            ref = jax.jit(jax.vmap(functools.partial(
                nms_ops.nms_reference, iou_threshold=th, max_out=mo,
                score_threshold=st)))
            pal = jax.jit(jax.vmap(functools.partial(
                pallas_nms.nms_pallas, iou_threshold=th, max_out=mo,
                score_threshold=st, block_size=64)))
            boxes, scores = make_cases(rng, 256, n,
                                       nan_frac=0.02 if ci == 0 else 0.0)
            assert_same_keeps(ref(boxes, scores), pal(boxes, scores),
                              context=f"config {ci}")

    def test_single_block_and_padding(self):
        rng = np.random.default_rng(11)
        for n, block in ((40, 64), (64, 64), (65, 64), (500, 128)):
            boxes, scores = make_cases(rng, 1, n)
            assert_same_keeps(
                nms_ops.nms_reference(boxes[0], scores[0], 0.5, 25),
                pallas_nms.nms_pallas(boxes[0], scores[0], 0.5, 25,
                                      block_size=block),
                f"n={n} block={block}")

    def test_all_suppressed(self):
        boxes = jnp.tile(jnp.asarray([[5., 5., 30., 30.]]), (100, 1))
        scores = jnp.linspace(0.0, 1.0, 100)
        idx, valid = pallas_nms.nms_pallas(boxes, scores, 0.5, 10,
                                           block_size=32)
        assert int(valid.sum()) == 1 and int(idx[0]) == 99


class TestMemory:
    """The inline jaxpr walk these tests used to carry now lives in
    ``analysis.jaxpr`` (one implementation, same bounds) — the linter's
    sibling auditor, also run by ``tools/check.py --jaxpr``."""

    def test_no_nxn_intermediate(self):
        """The blocked path's biggest intermediate is O(N*B), never N^2."""
        n, block = 4096, 256
        boxes = jnp.zeros((n, 4))
        scores = jnp.zeros((n,))
        biggest = ana_jaxpr.assert_peak_intermediate_below(
            functools.partial(nms_ops.nms_blocked, iou_threshold=0.5,
                              max_out=100, block_size=block),
            (boxes, scores), 4 * n * block, msg="O(N*B) budget")
        assert biggest < n * n // 2, \
            f"blocked NMS materializes a near-N^2 buffer ({biggest})"
        # sanity: the checker DOES see the reference's N x N buffer
        biggest_ref = ana_jaxpr.peak_intermediate(
            functools.partial(nms_ops.nms_reference, iou_threshold=0.5,
                              max_out=100), boxes, scores)
        assert biggest_ref >= n * n

    def test_pallas_wrapper_no_nxn(self):
        n = 2048
        biggest = ana_jaxpr.peak_intermediate(
            functools.partial(pallas_nms.nms_pallas, iou_threshold=0.5,
                              max_out=100, block_size=256),
            jnp.zeros((n, 4)), jnp.zeros((n,)))
        assert biggest < n * n // 2


def _bench(fn, *args, reps=3):
    out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
        times.append(time.perf_counter() - t0)
    return min(times)


# the suite compiles at a low XLA:CPU optimisation level (conftest.py); a
# timed program is compiled as it is deployed, at XLA's defaults
DEPLOYED = {"xla_backend_optimization_level": 2,
            "xla_cpu_use_fusion_emitters": True}


class TestWallClock:
    """CPU wall-clock acceptance (style of the prefetcher's 1.15x test
    in test_device_prefetch.py): the asymptotics must show up as real
    time even on the CPU backend."""

    def test_blocked_3x_faster_at_20k(self):
        rng = np.random.default_rng(20)
        n, mo = 20000, 100
        boxes, scores = make_cases(rng, 1, n, span=2000.0, wh_max=64.0)
        boxes, scores = boxes[0], scores[0]
        ref = jax.jit(functools.partial(nms_ops.nms_reference,
                                        iou_threshold=0.5, max_out=mo),
                      compiler_options=DEPLOYED)
        blk = jax.jit(functools.partial(nms_ops.nms_blocked,
                                        iou_threshold=0.5, max_out=mo),
                      compiler_options=DEPLOYED)
        assert_same_keeps(ref(boxes, scores), blk(boxes, scores),
                          "20k pre-timing")
        t_ref = _bench(ref, boxes, scores)
        t_blk = _bench(blk, boxes, scores)
        assert t_blk * 3 <= t_ref, \
            f"blocked {t_blk*1e3:.1f}ms not 3x faster than greedy " \
            f"{t_ref*1e3:.1f}ms at N={n}"

    def test_onepass_roi_align_beats_masked(self):
        rng = np.random.default_rng(21)
        pyr = {f"p{l}": jnp.asarray(rng.standard_normal(
            (256 >> (l - 2), 256 >> (l - 2), 64)).astype(np.float32))
            for l in (2, 3, 4, 5)}
        r = 1000
        ctr = rng.uniform(20, 480, (r, 2))
        size = np.exp(rng.uniform(np.log(8), np.log(400), (r, 2)))
        rois = jnp.asarray(np.clip(np.concatenate(
            [ctr - size / 2, ctr + size / 2], -1), 0, 511).astype(
                np.float32))
        one = jax.jit(lambda q: roi_ops.multiscale_roi_align(pyr, q),
                      compiler_options=DEPLOYED)
        msk = jax.jit(lambda q: roi_ops.multiscale_roi_align(
            pyr, q, impl="masked"), compiler_options=DEPLOYED)
        np.testing.assert_allclose(np.asarray(one(rois)),
                                   np.asarray(msk(rois)), atol=1e-5)
        t_one = _bench(one, rois)
        t_msk = _bench(msk, rois)
        assert t_one < t_msk, \
            f"one-pass {t_one*1e3:.1f}ms not faster than masked " \
            f"{t_msk*1e3:.1f}ms at R={r}"


class TestRoIAlignOnePass:
    def _pyramid_and_rois(self, seed=30, r=200, c=16):
        rng = np.random.default_rng(seed)
        pyr = {f"p{l}": jnp.asarray(rng.standard_normal(
            (128 >> (l - 2), 160 >> (l - 2), c)).astype(np.float32))
            for l in (2, 3, 4, 5)}
        ctr = rng.uniform(5, 250, (r, 2))
        size = np.exp(rng.uniform(np.log(6), np.log(240), (r, 2)))
        rois = np.clip(np.concatenate([ctr - size / 2, ctr + size / 2],
                                      -1), 0, 255).astype(np.float32)
        return pyr, jnp.asarray(rois)

    def test_parity_with_masked(self):
        pyr, rois = self._pyramid_and_rois()
        a = roi_ops.multiscale_roi_align(pyr, rois)
        b = roi_ops.multiscale_roi_align_masked(pyr, rois)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)

    def test_single_sampling_pass(self):
        """One-pass means ONE set of 4 corner gathers against the packed
        buffer — not 4 per level. The masked reference costs 4x."""
        pyr, rois = self._pyramid_and_rois(r=50)

        def count_gathers(fn):
            return ana_jaxpr.count_primitive(fn, "gather", rois)

        n_one = count_gathers(
            lambda q: roi_ops.multiscale_roi_align(pyr, q))
        n_msk = count_gathers(
            lambda q: roi_ops.multiscale_roi_align_masked(pyr, q))
        # 4 corner gathers + 4 tiny per-level table lookups
        assert n_one <= 8, f"one-pass does {n_one} gathers"
        assert n_msk >= 4 * len(pyr), \
            f"masked reference unexpectedly cheap ({n_msk} gathers)"

    def test_invalid_impl_raises(self):
        pyr, rois = self._pyramid_and_rois(r=4)
        with pytest.raises(ValueError):
            roi_ops.multiscale_roi_align(pyr, rois, impl="twopass")

    def test_torchvision_parity(self):
        torch = pytest.importorskip("torch")
        tv_ops = pytest.importorskip("torchvision.ops")
        rng = np.random.default_rng(31)
        feat = rng.standard_normal((32, 40, 8)).astype(np.float32)
        rois = np.asarray([[2.0, 3.0, 20.0, 18.0],
                           [0.0, 0.0, 39.0, 31.0],
                           [10.5, 7.25, 30.0, 28.5]], np.float32)
        ours = roi_ops.roi_align(jnp.asarray(feat), jnp.asarray(rois),
                                 output_size=7, spatial_scale=0.5,
                                 sampling_ratio=2)
        t_feat = torch.from_numpy(feat.transpose(2, 0, 1))[None]
        t_rois = torch.cat([torch.zeros(3, 1),
                            torch.from_numpy(rois)], dim=1)
        theirs = tv_ops.roi_align(t_feat, t_rois, output_size=7,
                                  spatial_scale=0.5, sampling_ratio=2)
        np.testing.assert_allclose(
            np.asarray(ours).transpose(0, 3, 1, 2),
            theirs.numpy(), atol=1e-4)


class TestSatellites:
    def test_gather_fill_padded_classes(self):
        """Regression: padded slots must not alias class-0/score-0."""
        idx = jnp.asarray([2, 0, 0])
        valid = jnp.asarray([True, False, False])
        boxes = jnp.arange(12.0).reshape(3, 4)
        scores = jnp.asarray([0.9, 0.8, 0.7])
        classes = jnp.asarray([0, 1, 2], jnp.int32)
        b, s, c = nms_ops.gather_nms_outputs(idx, valid, boxes, scores,
                                             classes, fill=(0, 0, -1))
        assert np.asarray(c).tolist() == [2, -1, -1]
        assert float(s[1]) == 0.0
        # scalar fill still applies everywhere (back-compat default)
        _, _, c0 = nms_ops.gather_nms_outputs(idx, valid, boxes, scores,
                                              classes)
        assert np.asarray(c0).tolist() == [2, 0, 0]
        with pytest.raises(ValueError):
            nms_ops.gather_nms_outputs(idx, valid, boxes, fill=(0, 1))

    def test_batched_nms_nan_box_does_not_poison(self):
        """Regression: one NaN/inf box must not poison every class
        offset (old max_coord = max(boxes) + 1)."""
        boxes = np.asarray([[0., 0., 10., 10.],
                            [100., 100., 110., 110.],
                            [np.nan, 0., 10., np.inf],
                            [50., 50., 60., 60.]], np.float32)
        scores = jnp.asarray([0.9, 0.8, 0.95, 0.7])
        classes = jnp.asarray([0, 1, 0, 1], jnp.int32)
        for impl in ("greedy", "blocked"):
            idx, valid = nms_ops.batched_nms(
                jnp.asarray(boxes), scores, classes, 0.5, 4,
                score_threshold=0.0, impl=impl)
            kept = set(np.asarray(idx)[np.asarray(valid)].tolist())
            # the three finite boxes are far apart -> all survive
            assert {0, 1, 3} <= kept, f"{impl}: finite boxes lost {kept}"

    def test_add_batch_matches_add_image(self):
        from deeplearning_tpu.evaluation.coco_eval import CocoEvaluator
        rng = np.random.default_rng(40)
        b, d, g, nc = 3, 6, 4, 3
        det = {
            "boxes": rng.uniform(0, 80, (b, d, 4)).astype(np.float32),
            "scores": rng.uniform(0, 1, (b, d)).astype(np.float32),
            "labels": rng.integers(0, nc, (b, d)),
            "valid": rng.uniform(size=(b, d)) < 0.7,
        }
        gt = {
            "boxes": rng.uniform(0, 80, (b, g, 4)).astype(np.float32),
            "labels": rng.integers(0, nc, (b, g)),
            "valid": rng.uniform(size=(b, g)) < 0.8,
        }
        det["boxes"][..., 2:] += det["boxes"][..., :2]
        gt["boxes"][..., 2:] += gt["boxes"][..., :2]
        # padded det slots carry the -1 class fill
        det["labels"][~det["valid"]] = -1

        ev1 = CocoEvaluator(nc, use_cpp=False)
        ev1.add_batch(np.arange(b), det, gt)
        ev2 = CocoEvaluator(nc, use_cpp=False)
        for j in range(b):
            dv = det["valid"][j]
            gv = gt["valid"][j]
            ev2.add_image(j, gt_boxes=gt["boxes"][j][gv],
                          gt_labels=gt["labels"][j][gv],
                          det_boxes=det["boxes"][j][dv],
                          det_scores=det["scores"][j][dv],
                          det_labels=det["labels"][j][dv])
        s1, s2 = ev1.summarize(), ev2.summarize()
        assert s1 == s2

    def test_add_batch_image_valid_mask(self):
        from deeplearning_tpu.evaluation.coco_eval import CocoEvaluator
        ev = CocoEvaluator(2, use_cpp=False)
        z4 = np.zeros((2, 1, 4))
        ev.add_batch([7, 8],
                     det={"boxes": z4, "scores": np.zeros((2, 1)),
                          "labels": -np.ones((2, 1), np.int64),
                          "valid": np.zeros((2, 1), bool)},
                     gt={"boxes": z4, "labels": np.zeros((2, 1)),
                         "valid": np.zeros((2, 1), bool)},
                     image_valid=[True, False])
        assert 7 in ev._gts and 8 not in ev._gts

    def test_postprocess_knob_greedy_vs_blocked(self):
        """The shared nms_impl knob: same detections either way (here on
        the yolox decoded surface every family shares)."""
        from deeplearning_tpu.models.detection.yolox import \
            postprocess_decoded
        rng = np.random.default_rng(41)
        dec = np.zeros((2, 400, 10), np.float32)
        ctr = rng.uniform(10, 100, (2, 400, 2))
        wh = rng.uniform(4, 30, (2, 400, 2))
        dec[..., 0:2] = ctr - wh / 2
        dec[..., 2:4] = ctr + wh / 2
        dec[..., 4:] = rng.normal(0, 2, (2, 400, 6))
        out_g = postprocess_decoded(jnp.asarray(dec), max_det=20,
                                    nms_impl="greedy")
        out_b = postprocess_decoded(jnp.asarray(dec), max_det=20,
                                    nms_impl="blocked")
        for k in ("boxes", "scores", "labels", "valid"):
            np.testing.assert_array_equal(np.asarray(out_g[k]),
                                          np.asarray(out_b[k]), k)
