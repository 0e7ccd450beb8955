"""The feed's batch route: an array-backed set stays in its storage dtype, a
batch is one gather, uint8 images cross the wire as uint8 and one jitted call
on the device makes them the float32 batch the step was compiled for — bit for
bit ``uint8.astype(float32) / float32(255)`` as numpy computes it, which is
what the benchmark's ``match_rows`` holds the program to."""

import json
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from benchmarks.harness import data as bench_data
from deeplearning_tpu.core.config import config_cli
from deeplearning_tpu.data import (ArraySource, DataLoader, DevicePrefetcher,
                                   ScaleUint8, uint8_to_unit)
from deeplearning_tpu.data.quarantine import QuarantineLog
from deeplearning_tpu.elastic import faults
from deeplearning_tpu.obs import flight
from deeplearning_tpu.parallel import MeshConfig, build_mesh
from deeplearning_tpu.parallel.sharding import batch_spec

ALL_U8 = np.arange(256, dtype=np.uint8)
WANT = ALL_U8.astype(np.float32) / np.float32(255.0)


def mesh_of(n):
    return build_mesh(MeshConfig(data=-1), devices=jax.devices()[:n])


def bits(x):
    return np.asarray(x).view(np.uint32)


def cfg_for(npz, batch, *extra):
    import train as train_cli
    return config_cli(train_cli.Config(), [
        f"data.npz={npz}", f"data.global_batch={batch}", "train.seed=5",
        *extra])


def build_loaders(cfg, mesh):
    import train as train_cli
    return train_cli._build_loaders(cfg, mesh)


def write_npz(tmp_path, images, labels):
    path = str(tmp_path / "set.npz")
    np.savez(path, images=images, labels=labels)
    return path


# ------------------------------------------------------------- (a) the bits
def _through_loader(prefetch):
    """All 256 values as one 256-pixel image per row, through a DataLoader
    with a mesh, alone (it transfers itself) or under a DevicePrefetcher."""
    images = np.stack([np.roll(ALL_U8, s) for s in range(8)]).reshape(
        8, 16, 16, 1)
    loader = DataLoader(
        ArraySource(image=images, label=np.arange(8, dtype=np.int32)),
        global_batch=8, shuffle=False, mesh=mesh_of(1),
        device_transform=ScaleUint8())
    feed = DevicePrefetcher(loader, depth=2) if prefetch else loader
    batch = next(iter(feed))
    assert isinstance(batch["image"], jax.Array)
    return np.asarray(batch["image"]).reshape(8, 256)[0]


@pytest.mark.parametrize("route", ["function", "loader", "prefetcher"])
def test_all_256_values_scale_to_numpys_bits(route):
    got = {"function": lambda: uint8_to_unit(ALL_U8),
           "loader": lambda: _through_loader(False),
           "prefetcher": lambda: _through_loader(True)}[route]()
    got = np.asarray(got)
    assert got.dtype == np.float32
    assert np.array_equal(bits(got), bits(WANT))
    assert got[0] == 0.0 and got[255] == 1.0


@pytest.mark.parametrize("shape", [(128, 224, 3), (3, 7, 5, 3), (1,)])
def test_random_uint8_scales_to_numpys_bits(shape):
    x = np.random.default_rng(0).integers(0, 256, shape, dtype=np.uint8)
    got = np.asarray(uint8_to_unit(x))
    assert got.shape == shape
    assert np.array_equal(bits(got),
                          bits(x.astype(np.float32) / np.float32(255.0)))


# ------------------------------------- (b) the cells' route, as they drive it
@pytest.mark.parametrize("infinite", [False, True],
                         ids=["finite", "endless"])
@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetcher", "loader_alone"])
def test_seeded_uint8_set_delivers_rows_the_benchmark_accepts(
        tmp_path, infinite, prefetch):
    seed, n, size, gb = 4261000001, 96, 16, 16
    images, labels = bench_data.image_set(seed, n, size, 10)
    cfg = cfg_for(write_npz(tmp_path, images, labels), gb, "data.val_rate=0",
                  "data.channels=3")
    mesh = mesh_of(1)
    loader, eval_loader, sample_shape, n_train = build_loaders(cfg, mesh)
    assert sample_shape == (1, size, size, 3) and n_train == n
    # no quarantine log on the cells' loaders: every batch is one gather
    assert loader.quarantine is None and eval_loader.quarantine is None
    assert isinstance(loader.source, ArraySource)
    assert loader.source.arrays["image"].dtype == np.uint8
    feed = DevicePrefetcher(loader, depth=2) if prefetch else loader
    # the tree's element_spec: the batch after the scaling
    want_sharding = NamedSharding(mesh, batch_spec())
    spec = feed.element_spec()
    assert spec["image"].shape == (gb, size, size, 3)
    assert spec["image"].dtype == np.float32
    assert spec["label"].shape == (gb,) and spec["label"].dtype == np.int32
    assert all(s.sharding == want_sharding for s in spec.values())
    assert eval_loader.element_spec()["image"].dtype == np.float32
    if infinite:
        feed.infinite = True     # the prefetcher drops what it had started
    index = bench_data.row_index(images)
    seen = []
    it = iter(feed)
    # 8 batches: the endless loader passes the set's end (6 a pass)
    for _ in range(8 if infinite else 3):
        batch = next(it)
        img = batch["image"]
        assert isinstance(img, jax.Array) and img.dtype == np.float32
        assert img.sharding.is_equivalent_to(want_sharding, img.ndim)
        got = bench_data.match_rows(np.asarray(img), np.asarray(batch["label"]),
                                    images, labels, index)
        assert got["wrong"] == 0
        seen += got["ids"]
    it.close()
    # a pass draws every row once: the gather follows the permutation
    assert len(set(seen[:n])) == len(seen[:n])
    if infinite:
        assert sorted(seen[:n]) == list(range(n))
        assert seen[n:] != seen[:len(seen) - n]      # reshuffled


# --------------------------- (c) grey-scale and channels, against the old fetch
def old_fetch(imgs, labs, channels):
    """The per-sample ``fetch`` that ``tools/train.py::_cls_source`` wrapped
    in a ``MapSource`` before PR 28, kept as the oracle."""
    def fetch(i):
        img = imgs[i]
        if img.dtype == np.uint8:
            img = img.astype(np.float32) / 255.0
        if img.ndim == 2:
            img = img[..., None]
        if img.shape[-1] == 1 and channels == 3:
            img = np.repeat(img, 3, axis=-1)
        return {"image": np.asarray(img, np.float32), "label": labs[i]}
    return fetch


SETS = {
    "grey_u8_to_1": ((40, 12, 12), np.uint8, 1),
    "grey_u8_to_3": ((40, 12, 12), np.uint8, 3),
    "one_channel_u8_to_3": ((40, 12, 12, 1), np.uint8, 3),
    "rgb_u8": ((40, 12, 12, 3), np.uint8, 3),
    "grey_f32_to_3": ((40, 12, 12), np.float32, 3),
    "one_channel_f64_to_3": ((40, 12, 12, 1), np.float64, 3),
    "rgb_u8_for_one_channel": ((40, 12, 12, 3), np.uint8, 1),
}


@pytest.mark.parametrize("name", sorted(SETS))
def test_batches_equal_what_the_per_sample_fetch_gave(tmp_path, name):
    shape, dtype, channels = SETS[name]
    rng = np.random.default_rng(7)
    images = (rng.integers(0, 256, shape).astype(dtype) if dtype == np.uint8
              else rng.normal(0, 1, shape).astype(dtype))
    labels = rng.integers(0, 10, shape[0]).astype(np.int32)
    cfg = cfg_for(write_npz(tmp_path, images, labels), 8, "data.val_rate=0",
                  f"data.channels={channels}")
    loader, eval_loader, sample_shape, _ = build_loaders(cfg, mesh_of(1))
    assert sample_shape == (1, 12, 12, channels)
    fetch = old_fetch(images, labels, channels)
    for ld in (loader, eval_loader):
        order = np.concatenate(list(ld._local_indices(ld.epoch)))
        got = list(DevicePrefetcher(ld, depth=2))
        assert len(got) == 5
        want = [fetch(int(i)) for i in order]
        for key in ("image", "label"):
            have = np.concatenate([np.asarray(b[key]) for b in got])
            old = np.stack([w[key] for w in want])
            assert have.dtype == old.dtype and have.shape == old.shape
            assert np.array_equal(have, old)
        assert ld.element_spec()["image"].shape == got[0]["image"].shape
        assert ld.element_spec()["image"].dtype == np.float32


# ------------------------------------------------ (d) the per-sample route
@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetcher", "loader_alone"])
def test_quarantine_log_keeps_the_per_sample_route(tmp_path, monkeypatch,
                                                   prefetch):
    flight.get_recorder().clear()
    images, labels = bench_data.image_set(11, 32, 8, 10)
    monkeypatch.setenv(faults.ENV_VAR, "bad_sample@step:5")
    faults.reset()
    try:
        qlog = QuarantineLog(str(tmp_path / "q.jsonl"))
        loader = DataLoader(ArraySource(image=images, label=labels),
                            global_batch=8, shuffle=False, mesh=mesh_of(1),
                            device_transform=ScaleUint8(), quarantine=qlog)
        feed = DevicePrefetcher(loader, depth=2) if prefetch else loader
        batches = list(feed)
    finally:
        faults.reset()
    assert len(batches) == 4 and qlog.quarantined == 1
    row = json.loads(open(tmp_path / "q.jsonl").readline())
    assert "InjectedBadSample" in row["error"] and row["index"] == 4
    first = np.asarray(batches[0]["image"])
    want = images[:8].astype(np.float32) / np.float32(255.0)
    # the fifth fetch was substituted by a good row of the batch; the rest
    # are the set's rows, scaled like every other batch
    assert np.array_equal(np.delete(first, 4, axis=0), np.delete(want, 4, 0))
    assert any(np.array_equal(first[4], w) for w in want)
    assert np.array_equal(np.asarray(batches[1]["image"]),
                          images[8:16].astype(np.float32) / np.float32(255.0))
    (event,) = flight.get_recorder().events("feed")
    assert event["route"] == "per_sample" and event["calls"] == 4


# --------------------------------------------------- (e) across four devices
@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetcher", "loader_alone"])
def test_scaled_batch_keeps_batch_sharding_on_four_devices(tmp_path, prefetch):
    mesh = mesh_of(4)
    images, labels = bench_data.image_set(3, 64, 8, 10)
    cfg = cfg_for(write_npz(tmp_path, images, labels), 16, "data.val_rate=0",
                  "data.channels=3")
    loader, _, _, _ = build_loaders(cfg, mesh)
    feed = DevicePrefetcher(loader, depth=2) if prefetch else loader
    want = NamedSharding(mesh, batch_spec())
    assert feed.element_spec()["image"].sharding == want
    index = bench_data.row_index(images)
    for batch in feed:
        img = batch["image"]
        assert img.dtype == np.float32 and img.shape == (16, 8, 8, 3)
        assert img.sharding.is_equivalent_to(want, img.ndim)
        assert {s.data.shape for s in img.addressable_shards} == {(4, 8, 8, 3)}
        assert len({s.device for s in img.addressable_shards}) == 4
        assert bench_data.match_rows(
            np.asarray(img), np.asarray(batch["label"]), images, labels,
            index)["wrong"] == 0


# ------------------------------------------------------- the flight event
@pytest.mark.parametrize("case", ["uint8_gather", "float_gather"])
def test_one_feed_event_per_loader_counts_its_batches(tmp_path, case):
    flight.get_recorder().clear()
    images, labels = bench_data.image_set(3, 48, 8, 10)
    if case == "float_gather":
        images = images.astype(np.float32)
    cfg = cfg_for(write_npz(tmp_path, images, labels), 16, "data.val_rate=0",
                  "data.channels=3")
    loader, eval_loader, _, _ = build_loaders(cfg, mesh_of(1))
    assert len(list(DevicePrefetcher(loader, depth=2))) == 3
    assert len(list(loader)) == 3            # a second pass, the same event
    assert len(list(eval_loader)) == 3
    events = flight.get_recorder().events("feed")
    assert len(events) == 2
    train_event, eval_event = events
    assert train_event["calls"] == 6 and eval_event["calls"] == 3
    item = 1 if case == "uint8_gather" else 4
    for e in events:
        assert e["route"] == "array_gather" and e["batch"] == 16
        assert e["scaled_on"] == ("device" if item == 1 else "host")
        assert e["wire_dtype"] == {
            "image": "uint8" if item == 1 else "float32", "label": "int32"}
        assert e["wire_bytes"] == 16 * 8 * 8 * 3 * item + 16 * 4
    json.dumps(events)                       # flightrec.json can hold them


# ------------------------------------- the step the batch is compiled for
def _trained(tmp_path, name, images, labels):
    import train as train_cli
    path = str(tmp_path / f"{name}.npz")
    np.savez(path, images=images, labels=labels)
    trainer = train_cli.build_trainer(config_cli(train_cli.Config(), [
        "model.name=mnist_fcn", f"data.npz={path}", "data.global_batch=32",
        "data.val_rate=0", "train.epochs=1", "train.seed=3"]))
    seen = []
    trainer.callbacks.register(
        "before_iter", lambda tr, batch: seen.append(batch["image"]))
    trainer.train()
    return trainer, seen


def test_uint8_set_trains_the_step_a_float32_set_trains(tmp_path):
    """The same images stored as uint8 and as float32 already scaled: the
    step is lowered for the same float32 batch, the callbacks see the same
    bits and training ends in the same parameters."""
    flight.get_recorder().clear()
    rng = np.random.default_rng(1)
    images = rng.integers(0, 256, (128, 28, 28), dtype=np.uint8)
    labels = rng.integers(0, 10, 128).astype(np.int32)
    as_u8, seen_u8 = _trained(tmp_path, "u8", images, labels)
    as_f32, seen_f32 = _trained(
        tmp_path, "f32", images.astype(np.float32) / np.float32(255.0),
        labels)
    assert not flight.get_recorder().events("retrace")
    assert len(seen_u8) == len(seen_f32) == 4
    for a, b in zip(seen_u8, seen_f32):
        assert a.dtype == np.float32 and a.shape == (32, 28, 28, 1)
        assert np.array_equal(bits(a), bits(b))
    spec_u8 = as_u8.train_loader.element_spec()
    assert spec_u8 == as_f32.train_loader.element_spec()
    assert as_u8._aot_step.as_text() == as_f32._aot_step.as_text()
    for a, b in zip(jax.tree.leaves(as_u8.state.params),
                    jax.tree.leaves(as_f32.state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert as_u8.evaluate() == as_f32.evaluate()
    routes = {(e["scaled_on"], e["wire_dtype"]["image"])
              for e in flight.get_recorder().events("feed") if "route" in e}
    assert routes == {("device", "uint8"), ("host", "float32")}
