#!/usr/bin/env python
"""Unified training CLI — the successor of every per-project train.py.

Usage:
  python tools/train.py --cfg configs/vit_b16.yaml [key value ...]
  python tools/train.py model.name=resnet50 data.synthetic=true train.epochs=2

One entry point drives the whole zoo through the registry + Trainer
(SURVEY.md §1.1: archetypes A/B/C collapse into config + hooks). Data
comes from npz/folder sources or the built-in synthetic generator (for
smoke tests; the reference bundles tiny datasets for the same purpose).
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    name: str = "mnist_cnn"
    num_classes: int = 10
    precision: str = "bf16"          # bf16 | f32
    exact_gelu: bool = False         # erf GELU (torch parity; −3.8 MFU)


@dataclasses.dataclass(frozen=True)
class DataCfg:
    folder: Optional[str] = None     # ImageFolder root (real JPEG path)
    npz: Optional[str] = None        # npz with images/labels arrays
    synthetic: bool = True
    image_size: int = 28
    channels: int = 1
    n_train: int = 512
    global_batch: int = 64
    val_rate: float = 0.2            # folder-mode train/val split
    num_workers: int = 8             # folder-mode decode threads
    augment: str = "imagenet"        # imagenet | light | none
    prefetch: int = 2                # device-feed queue depth (0 = off)
    seq_len: int = 128               # synthetic token rows (language models)


@dataclasses.dataclass(frozen=True)
class OptimCfg:
    name: str = "sgd"
    lr: float = 0.05
    weight_decay: float = 0.0
    momentum: float = 0.9
    schedule: str = "warmup_cosine"
    warmup_steps: int = 10
    clip_grad_norm: float = 0.0


@dataclasses.dataclass(frozen=True)
class TrainCfg:
    epochs: int = 3
    seed: int = 0
    label_smoothing: float = 0.0
    ema: bool = False
    workdir: Optional[str] = None
    mesh_model_axis: int = 1         # >1 enables tensor parallelism
    mesh_seq_axis: int = 1           # >1 enables sequence parallelism
    seq_parallel: str = "ring"       # ring | ulysses (transformers only)
    accum_steps: int = 1             # gradient accumulation microbatches
    mixup: bool = False              # mixup/cutmix soft targets
    async_checkpoint: bool = False   # overlap Orbax writes with training
    pipeline_stages: int = 1         # >1: GPipe pipeline over 'model' axis
                                     # (ViT family; blocks split S-ways)
    microbatches: int = 0            # pipeline microbatches (0 = stages)
    donate_batch: bool = True        # recycle input HBM buffers per step
    precompile: bool = True          # AOT step compile overlapped w/ feed
    recovery: str = "none"           # none|abort: raise on divergence;
                                     # rollback: anchor + skip + cooldown
    strict: str = ""                 # ""|transfers|nans|all: arm JAX
                                     # sanitizers (see analysis.strict)
    weight_update: str = "replicated"  # replicated | zero1: shard adam
                                     # moments over the data axes (ZeRO-1)
    grad_comm: str = "fp32"          # fp32 | int8: EQuARX block-scaled
                                     # int8 gradient collectives


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelCfg = dataclasses.field(default_factory=ModelCfg)
    data: DataCfg = dataclasses.field(default_factory=DataCfg)
    optim: OptimCfg = dataclasses.field(default_factory=OptimCfg)
    train: TrainCfg = dataclasses.field(default_factory=TrainCfg)


def load_data(cfg: DataCfg, num_classes: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    if cfg.npz:
        blob = np.load(cfg.npz)
        # raw storage (often uint8 single-channel); conversion to model
        # f32/RGB happens a batch at a time in the feed (_build_loaders:
        # channels on the host, uint8 scaled on the device), NOT here — an
        # eager convert would hold a 12x float copy of the whole dataset
        return blob["images"], blob["labels"]
    rng = np.random.default_rng(0)
    n, s, c = cfg.n_train, cfg.image_size, cfg.channels
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    images = rng.normal(0, 0.1, (n, s, s, c)).astype(np.float32)
    block = max(s // num_classes, 1)
    for i, lab in enumerate(labels):
        images[i, :, lab * block:(lab + 1) * block, 0] += 2.0
    return images, labels


def model_task(name: str) -> str:
    """What the registry entry says it is: ``language`` (token rows, the
    loss of ``train/language.py``) or, unsaid, ``classification``."""
    from deeplearning_tpu.core.registry import MODELS
    return getattr(MODELS.get(name), "task", "classification")


def _token_loaders(cfg: Config, mesh):
    """Token rows ``(rows, S + 1)`` int32 from the npz's ``tokens`` (or drawn
    uniformly over the vocabulary held), through the same ``ArraySource``
    route as the image sets: a batch is one gather, int32 on the wire."""
    from deeplearning_tpu.data import ArraySource, DataLoader

    if cfg.data.folder:
        raise ValueError("a language model reads token rows from data.npz")
    if cfg.data.npz:
        tokens = np.load(cfg.data.npz)["tokens"]
    else:
        tokens = np.random.default_rng(0).integers(
            0, cfg.model.num_classes, (cfg.data.n_train, cfg.data.seq_len + 1))
    tokens = np.ascontiguousarray(tokens, np.int32)
    gb = cfg.data.global_batch
    n_val = 0
    if cfg.data.val_rate > 0 and len(tokens) >= 2 * gb:
        n_val = min(max(int(len(tokens) * cfg.data.val_rate), gb),
                    len(tokens) - gb)
    loader = DataLoader(ArraySource(tokens=tokens[n_val:]), global_batch=gb,
                        mesh=mesh, seed=cfg.train.seed)
    eval_loader = DataLoader(ArraySource(tokens=tokens[:n_val or len(tokens)]),
                             global_batch=gb, mesh=mesh, shuffle=False)
    # the parameters do not depend on the sequence: a short row initialises
    return loader, eval_loader, (1, min(tokens.shape[1] - 1, 16)), \
        len(tokens) - n_val


def _build_loaders(cfg: Config, mesh):
    """Train and eval loaders from the folder, the npz or the synthetic
    set: ``(loader, eval_loader, sample_shape, n_train)``."""
    from deeplearning_tpu.data import ArraySource, DataLoader, ScaleUint8

    if model_task(cfg.model.name) == "language":
        return _token_loaders(cfg, mesh)
    if cfg.data.folder:
        from deeplearning_tpu.data.build import (LoaderConfig,
                                                 build_classification_loaders)
        lcfg = LoaderConfig(global_batch=cfg.data.global_batch,
                            image_size=cfg.data.image_size,
                            val_rate=cfg.data.val_rate,
                            num_workers=cfg.data.num_workers,
                            seed=cfg.train.seed,
                            augment=cfg.data.augment)
        loader, eval_loader, class_to_idx = build_classification_loaders(
            cfg.data.folder, lcfg, mesh=mesh,
            class_indices_path=(os.path.join(cfg.train.workdir,
                                             "class_indices.json")
                                if cfg.train.workdir else None))
        if len(class_to_idx) != cfg.model.num_classes:
            raise ValueError(
                f"model.num_classes={cfg.model.num_classes} but "
                f"{cfg.data.folder} has {len(class_to_idx)} classes")
        sample_shape = (1, cfg.data.image_size, cfg.data.image_size, 3)
        n_train = len(loader) * cfg.data.global_batch
        return loader, eval_loader, sample_shape, n_train
    images, labels = load_data(cfg.data, cfg.model.num_classes)
    hw = images.shape[1:3]
    sample_shape = (1, hw[0], hw[1], cfg.data.channels)
    tr_images, tr_labels = images, labels
    ev_images, ev_labels = images, labels
    gb = cfg.data.global_batch
    if cfg.data.npz and cfg.data.val_rate > 0 and len(images) >= 2 * gb:
        # held-out split for npz datasets, BEFORE the schedule is
        # sized (total_steps must match the post-split loader) and
        # never smaller than one eval batch (the loader floor-divides,
        # so a sub-batch slice would silently eval nothing)
        order = np.random.default_rng(cfg.train.seed).permutation(
            len(images))
        n_val = min(max(int(len(images) * cfg.data.val_rate), gb),
                    len(images) - gb)
        ev_images, ev_labels = (images[order[:n_val]],
                                labels[order[:n_val]])
        tr_images, tr_labels = (images[order[n_val:]],
                                labels[order[n_val:]])
    n_train = len(tr_images)

    def _cls_loader(imgs, labs, **kw):
        """The set stays in its storage dtype inside an ``ArraySource``
        and a batch is one gather. Grey-scale ``(N, H, W)`` sets and
        1 → 3 channels are expanded a batch at a time on the host;
        uint8 images cross the wire as uint8 and are scaled to float32
        in [0, 1] on the device (``ScaleUint8``), so the step, mixup
        and the eval step get the float32 batch they always got."""
        def expand(batch):
            img = batch["image"]
            if img.dtype != np.uint8:
                img = np.asarray(img, np.float32)
            if img.ndim == 3:
                img = img[..., None]
            if img.shape[-1] == 1 and cfg.data.channels == 3:
                img = np.repeat(img, 3, axis=-1)
            return {**batch, "image": img}
        is_u8 = imgs.dtype == np.uint8
        needs = imgs.ndim == 3 or imgs.shape[-1] != cfg.data.channels
        return DataLoader(ArraySource(image=imgs, label=labs),
                          global_batch=cfg.data.global_batch, mesh=mesh,
                          transform=expand if needs else None,
                          device_transform=ScaleUint8() if is_u8 else None,
                          **kw)

    loader = _cls_loader(tr_images, tr_labels, seed=cfg.train.seed)
    eval_loader = _cls_loader(ev_images, ev_labels, shuffle=False)
    return loader, eval_loader, sample_shape, n_train


def build_trainer(cfg: Config, devices=None):
    """Everything ``main`` does up to the first step: mesh (over
    ``devices``, default all of them), data, model, sharded state,
    jitted step, ``Trainer`` and (by default) the AOT step compile.
    Returned un-run so a caller can hook the loop — ``chip_smoke.py``
    drives the chip through exactly this path. Its parts are run-level
    phases (``setup/mesh`` ... ``setup/posture``, with ``setup/lower`` and
    ``compile/train_step`` inside ``Trainer.precompile``): they tile the
    call, and ``obs.spans.phases()`` says where its seconds went."""
    from deeplearning_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()   # step compiles are once-per-machine, not per-run
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.obs.spans import phase
    from deeplearning_tpu.parallel import MeshConfig, build_mesh
    from deeplearning_tpu.train import (TrainState, make_eval_step,
                                        make_train_step, shard_state)
    from deeplearning_tpu.train import classification, language
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule
    from deeplearning_tpu.train.trainer import Trainer

    pp_stages = cfg.train.pipeline_stages
    lm = model_task(cfg.model.name) == "language"
    if lm and (pp_stages > 1 or cfg.train.mixup or cfg.train.label_smoothing
               or cfg.train.mesh_seq_axis > 1):
        raise ValueError("a language model trains without pipeline_stages, "
                         "mixup, label_smoothing and mesh_seq_axis")
    if pp_stages > 1 and (cfg.train.mesh_model_axis > 1
                          or cfg.train.mesh_seq_axis > 1):
        raise ValueError("train.pipeline_stages reuses the 'model' mesh "
                         "axis; unset mesh_model_axis/mesh_seq_axis")
    if pp_stages > 1 and (cfg.train.mixup or cfg.train.ema
                          or cfg.train.accum_steps > 1):
        raise ValueError("pipeline_stages does not compose with "
                         "mixup/ema/accum_steps yet")
    if cfg.train.weight_update not in ("replicated", "zero1"):
        raise ValueError(f"train.weight_update="
                         f"{cfg.train.weight_update!r} (replicated | zero1)")
    if cfg.train.grad_comm not in ("fp32", "int8"):
        raise ValueError(f"train.grad_comm={cfg.train.grad_comm!r} "
                         "(fp32 | int8)")
    zero1 = cfg.train.weight_update == "zero1"
    if (zero1 or cfg.train.grad_comm == "int8") and (
            pp_stages > 1 or cfg.train.mesh_model_axis > 1
            or cfg.train.mesh_seq_axis > 1):
        raise ValueError("train.weight_update=zero1 / train.grad_comm=int8 "
                         "are data-parallel modes; unset pipeline_stages/"
                         "mesh_model_axis/mesh_seq_axis")
    if cfg.train.grad_comm == "int8" and cfg.train.accum_steps > 1:
        raise ValueError("train.grad_comm=int8 requires "
                         "train.accum_steps=1 (quantizing microbatch "
                         "partial sums would stack quantization error)")
    with phase("setup/mesh"):
        mesh = build_mesh(MeshConfig(
            data=-1,
            model=pp_stages if pp_stages > 1 else cfg.train.mesh_model_axis,
            seq=cfg.train.mesh_seq_axis), devices=devices)
    if pp_stages > 1 and mesh.shape["data"] > 1:
        print(f"WARNING: pipeline_stages={pp_stages} uses only the "
              f"{pp_stages}-device 'model' axis; the {mesh.shape['data']}"
              "-way 'data' axis replicates work (DPxPP composition not "
              "implemented yet) — set pipeline_stages = device count")
    with phase("setup/data"):
        loader, eval_loader, sample_shape, n_train = _build_loaders(
            cfg, mesh)
    dtype = jnp.bfloat16 if cfg.model.precision == "bf16" else jnp.float32
    if cfg.model.exact_gelu:
        from deeplearning_tpu.core import numerics
        numerics.set_exact(True)
    model_kw = {}
    if cfg.train.seq_parallel not in ("ring", "ulysses"):
        raise ValueError(
            f"unknown train.seq_parallel={cfg.train.seq_parallel!r} "
            "(ring | ulysses)")
    if cfg.train.mesh_seq_axis > 1:
        # sequence parallelism INSIDE the model: every attention layer
        # shards its tokens over the 'seq' mesh axis (ring rotation or
        # Ulysses all-to-all) while batch/params stay GSPMD-sharded.
        # Transformers only — the builder must accept attn_fn.
        if cfg.train.seq_parallel == "ring":
            from deeplearning_tpu.parallel.ring_attention import (
                make_ring_attn_fn)
            model_kw["attn_fn"] = make_ring_attn_fn(mesh)
        else:
            from deeplearning_tpu.parallel.ulysses import (
                make_ulysses_attn_fn)
            model_kw["attn_fn"] = make_ulysses_attn_fn(mesh)
    with phase("setup/model_init"):
        model = MODELS.build(cfg.model.name,
                             num_classes=cfg.model.num_classes,
                             dtype=dtype, **model_kw)
        sample = jnp.zeros(sample_shape, jnp.int32 if lm else jnp.float32)
        # a decoder's 700 M parameters in one compiled program; run
        # eagerly, layer by layer, the draw took 4 min of a cold set-up on
        # the chip (PR 32). The image models keep the eager pass their
        # cells were measured with.
        init = jax.jit(model.init, static_argnames="train") if lm \
            else model.init
        variables = init(jax.random.key(cfg.train.seed), sample, train=False)
        params = variables["params"]
        k_per_stage = 0
        if pp_stages > 1:
            from deeplearning_tpu.parallel.pipeline_train import \
                split_vit_params
            outer, stages, k_per_stage = split_vit_params(params, pp_stages)
            params = {"outer": outer, "stages": stages}
    with phase("setup/state"):
        steps_per_epoch = n_train // cfg.data.global_batch
        sched = build_schedule(cfg.optim.schedule, base_lr=cfg.optim.lr,
                               total_steps=cfg.train.epochs * steps_per_epoch,
                               warmup_steps=cfg.optim.warmup_steps)
        tx = build_optimizer(cfg.optim.name, sched,
                             clip_grad_norm=cfg.optim.clip_grad_norm or None,
                             weight_decay=cfg.optim.weight_decay,
                             momentum=cfg.optim.momentum, params=params)
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=tx,
            batch_stats=variables.get("batch_stats", {}),
            use_ema=cfg.train.ema)

        if pp_stages > 1:
            from deeplearning_tpu.parallel.pipeline_train import \
                shard_pipeline_state
            state = shard_pipeline_state(state, mesh)
        else:
            state = shard_state(state, mesh, zero1=zero1)
    has_bn = bool(variables.get("batch_stats"))
    if cfg.data.global_batch % max(cfg.train.accum_steps, 1):
        raise ValueError(
            f"data.global_batch={cfg.data.global_batch} must be divisible "
            f"by train.accum_steps={cfg.train.accum_steps}")
    with phase("setup/step_build"):
        if pp_stages > 1:
            from deeplearning_tpu.parallel.pipeline_train import \
                make_pipeline_train_step
            micro = cfg.train.microbatches or pp_stages
            if micro % pp_stages:
                raise ValueError(
                    f"train.microbatches={micro} must be divisible by "
                    f"train.pipeline_stages={pp_stages} (microbatch storage "
                    "shards over the pipe axis)")
            if cfg.data.global_batch % micro:
                raise ValueError(
                    f"data.global_batch={cfg.data.global_batch} must be "
                    f"divisible by train.microbatches={micro}")
            base_step, pp_eval_step = make_pipeline_train_step(
                model, mesh, tx, num_stages=pp_stages,
                k_per_stage=k_per_stage, microbatches=micro,
                label_smoothing=cfg.train.label_smoothing)
        else:
            base_step = make_train_step(
                language.make_loss_fn() if lm else
                classification.make_loss_fn(cfg.train.label_smoothing,
                                            has_bn), mesh=mesh,
                accum_steps=cfg.train.accum_steps,
                donate_batch=cfg.train.donate_batch,
                weight_update=cfg.train.weight_update,
                grad_comm=cfg.train.grad_comm)
        if cfg.train.mixup:
            from deeplearning_tpu.core import rng as rng_mod
            from deeplearning_tpu.data.mixup import mixup_cutmix

            def train_step(s, batch, rng):
                # fold the step in HERE: the Trainer hands the same run key
                # every iteration (step-folding otherwise happens inside
                # base_step, after augmentation would already have run)
                aug_key = rng_mod.step_key(jax.random.fold_in(rng, 1), s.step)
                batch = mixup_cutmix(batch, aug_key, cfg.model.num_classes,
                                     smoothing=cfg.train.label_smoothing)
                return base_step(s, batch, rng)
            train_step = jax.jit(
                train_step,
                donate_argnums=(0, 1) if cfg.train.donate_batch else (0,))
        else:
            train_step = base_step
        trainer = Trainer(
            state=state,
            train_step=train_step,
            train_loader=loader,
            eval_step=(pp_eval_step if pp_stages > 1
                       else make_eval_step(
                           language.make_metric_fn() if lm
                           else classification.make_metric_fn())),
            eval_loader=eval_loader,
            epochs=cfg.train.epochs,
            seed=cfg.train.seed,
            workdir=cfg.train.workdir,
            async_checkpoint=cfg.train.async_checkpoint,
            log_every=max(steps_per_epoch // 2, 1),
            prefetch=cfg.data.prefetch,
            recovery=(None if cfg.train.recovery in ("none", "")
                      else cfg.train.recovery),
            strict=cfg.train.strict or None,
            weight_update=cfg.train.weight_update,
            # full config into the flight recorder: a flightrec.json from a
            # crashed run identifies the exact run that produced it
            run_config=dataclasses.asdict(cfg))
    if cfg.train.precompile:
        try:
            # AOT step compile runs while the prefetcher's worker thread
            # decodes + transfers the first batches — neither serializes
            # behind the other
            trainer.precompile()
        except Exception as e:  # noqa: BLE001 - warmup is best-effort
            print(f"precompile skipped: {e}")
    # sharding posture into the flight ring (obs_report renders it):
    # which weight-update mode this run uses and — when the AOT step is
    # available — how many collective bytes one step moves
    try:
        from deeplearning_tpu.obs import flight
        posture = {"weight_update": cfg.train.weight_update,
                   "grad_comm": cfg.train.grad_comm}
        aot = getattr(trainer, "_aot_step", None)
        if aot is not None:
            from deeplearning_tpu.analysis.jaxpr import hlo_collective_bytes
            # parses the compiled step's text: a phase of its own
            with phase("setup/posture"):
                posture["collective_bytes"] = sum(
                    hlo_collective_bytes(aot).values())
        flight.record("sharding", **posture)
    # dltpu: allow(DLT104) posture is observability only, never fail a run
    except Exception:  # noqa: BLE001
        pass
    return trainer


def main(argv=None) -> int:
    from deeplearning_tpu.core.config import config_cli
    from deeplearning_tpu.elastic import EXIT_PREEMPTED, Preempted

    trainer = build_trainer(config_cli(Config(), argv, description=__doc__))
    try:
        trainer.train()
    except Preempted:
        # checkpoint + flight ring already flushed by the Trainer; 75
        # tells the supervisor "requeue me", not "I crashed"
        return EXIT_PREEMPTED
    results = trainer.evaluate()
    print({k: round(v, 4) for k, v in results.items()})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
