"""What the tests of the step's scopes share (``train/steps.py::STEP_SCOPES``
read off a compiled step's ``op_name``s): counts, on the CPU. The rule for
"unplaced" and the metrics' patterns are the benchmark's own files, so a test
here fails when program and benchmark part."""

import contextlib
import json
import os
import re
import types

import jax
import numpy as np

from benchmarks.harness.trace import _INSTRUCTION, op_paths
from benchmarks.readers import unplaced_pct

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "metrics")


def metric_params(name: str) -> dict:
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)["params"]


@contextlib.contextmanager
def fresh_compiles():
    """JAX's persistent cache keys a program without its metadata: a step
    compiled before a scope was added comes back from it with the old
    ``op_name``s. A test that reads names compiles its step afresh."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def program_paths(text: str) -> list:
    """The op paths the program's own trace wrote: a parameter is named by
    its argument and what XLA's CPU passes expand by the bare primitive,
    neither starts at ``jit(``."""
    return [p for p in op_paths(text).values() if p.startswith("jit(")]


def under(paths: list, scope: str) -> tuple:
    """(forward, backward) paths under ``scope``: JAX writes a scope that is
    the first under a transformation as ``jvp(scope)``."""
    rx = re.compile(rf"[/(]{scope}[/)]")
    hit = [p for p in paths if rx.search(p)]
    return ([p for p in hit if "transpose(" not in p],
            [p for p in hit if "transpose(" in p])


def unplaced_primitives(paths: list) -> set:
    wrappers = re.compile(metric_params("step_unplaced_pct")["wrappers"])
    return {p.split(";")[0].split("/")[-1] for p in paths
            if not unplaced_pct.placed(p, wrappers)}


def instruction_count(text: str) -> int:
    return sum(1 for line in text.splitlines() if _INSTRUCTION.match(line))


def two_steps(model_name: str, tmp) -> types.SimpleNamespace:
    """``tools/train.py::build_trainer``'s trainer for a micro decoder after
    two steps of ``Trainer.train`` on a token npz, its step compiled afresh:
    the trainer, the steps' metrics, the flight ring's ``kernel`` and
    ``feed`` events and the step's op paths. One build a test file."""
    import train as train_cli
    from deeplearning_tpu.core.config import config_cli
    from deeplearning_tpu.obs import flight
    tokens = np.random.default_rng(0).integers(0, 512, (16, 33), np.int32)
    np.savez(tmp / "data.npz", tokens=tokens)
    recorder = flight.get_recorder()
    recorder.clear()
    seen = []
    with fresh_compiles():
        trainer = train_cli.build_trainer(config_cli(train_cli.Config(), [
            f"model.name={model_name}", "model.num_classes=512",
            f"data.npz={tmp / 'data.npz'}", "data.synthetic=false",
            "data.global_batch=8", "data.val_rate=0", "optim.name=adamw",
            "optim.lr=1e-3", "optim.clip_grad_norm=1.0", "train.epochs=1"]),
            devices=jax.devices()[:1])
        trainer.callbacks.register(
            "after_iter",
            lambda tr, metrics: seen.append(jax.device_get(metrics)))
        trainer.train()
        trainer.close_feed()
    return types.SimpleNamespace(
        trainer=trainer, seen=seen, kernels=recorder.events("kernel"),
        feeds=recorder.events("feed"),
        paths=program_paths(trainer.compiled_step_text()))


def check_decoder_vocabulary(paths: list) -> None:
    """``STEP_SCOPES`` on a decoder's step as ``build_trainer`` compiles it:
    the loss head and the attention's glue forward and backward, the weight
    update forward only; float32 parameters leave ``grad_cast`` nothing to
    do and the decoders train without a moving average
    (``tests/test_train_slice.py`` has both)."""
    from deeplearning_tpu.train.steps import STEP_SCOPES
    found = {s: under(paths, s) for s in STEP_SCOPES}
    for scope in ("loss_head", "rotary", "head_split"):
        assert found[scope][0] and found[scope][1], scope
    for scope in ("optimizer", "step_metrics"):
        assert found[scope][0] and not found[scope][1], scope
    assert found["grad_cast"] == found["ema"] == ([], [])
    # the blocked loss's scan, both directions, is the loss head's (one
    # block at this size, so XLA drops the loop and keeps its body's ops)
    body = [p for p in paths if "/while/body/" in p and "/moe/" not in p]
    assert body and all(re.search(r"\(loss_head\)+/while/body/", p)
                        for p in body)
    assert {"transpose(" in p for p in body} == {False, True}
    # the benchmark's patterns find what they are to read
    for metric in ("loss_head_ms", "weight_update_ms", "attention_glue_ms"):
        rx = re.compile(metric_params(metric)["pattern"])
        assert any(rx.search(p) for p in paths), metric


def check_glue_and_unplaced(paths: list, cores: tuple) -> None:
    """``rotary`` / ``head_split`` never inside a kernel's scope (the
    accepted metrics' patterns match what they matched), and what no module
    and no scope places is a short list, by primitive: the token slices and
    the means of ``loss_fn``, ``state.step + 1``, the remat call's own
    plumbing. Top-level work a later PR adds without a name lands here."""
    scopes = "|".join(cores + ("moe_dispatch", "moe_combine",
                               "expert_matmul", "expert_act"))
    inside = re.compile(rf"/({scopes})/.*(rotary|head_split)")
    for core in cores:
        assert any(f"/attn/{core}/" in p for p in paths), core
    assert not [p for p in paths if inside.search(p)]
    glue = re.compile(metric_params("attention_glue_ms")["pattern"])
    assert not [p for p in paths if glue.search(p) and "_core" in p]
    assert unplaced_primitives(paths) <= {
        "slice", "div", "add", "convert_element_type", "remat2"}
