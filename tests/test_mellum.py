"""Mellum2's decoder (``models/language/mellum.py``: grouped-query attention,
sliding-window layers among full ones, softmax top-k experts in every layer),
the windowed and grouped causal kernels, ``softmax_route`` and the one-head
language task, at a small size on the CPU with seeded weights, against the
benchmark's plain reference (``benchmarks/references/mellum.py``), which
imports nothing of the program."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

import _step_scopes                                           # noqa: E402
from benchmarks.flops import mellum as flops                  # noqa: E402
from benchmarks.references import mellum as ref               # noqa: E402
from benchmarks.references import ops as ref_ops              # noqa: E402
from benchmarks.references import train_ref_lm                # noqa: E402
from deeplearning_tpu.analysis import jaxpr as audit          # noqa: E402
from deeplearning_tpu.core.registry import MODELS             # noqa: E402
from deeplearning_tpu.models.language import mellum           # noqa: E402
from deeplearning_tpu.obs import flight                       # noqa: E402
from deeplearning_tpu.ops.pallas import flash_attention as flash  # noqa: E402
from deeplearning_tpu.parallel import moe                     # noqa: E402
from deeplearning_tpu.train import language                   # noqa: E402
from deeplearning_tpu.train.state import TrainState           # noqa: E402

# the registry's mellum_micro, as the reference's shapes
SHAPES = json.load(open(os.path.join(
    ROOT, "benchmarks", "tests", "configs", "mellum_micro.json")))["shapes"]
SHAPES = {**SHAPES, "seq_len": 32}
SEED = 13
KINDS = (mellum.SLIDING, mellum.FULL)


@pytest.fixture(scope="module")
def setup():
    model = MODELS.build("mellum_micro", dtype=jnp.float32)
    params = ref.make_params(ref.param_spec(SHAPES), SEED)
    rows = jnp.asarray(np.random.default_rng(SEED).integers(
        0, SHAPES["vocab_size"], (4, SHAPES["seq_len"] + 1)), jnp.int32)
    return model, params, rows


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-12), (
        np.abs(a - b).max(), np.abs(b).max())


def test_program_and_reference_share_one_parameter_tree(setup):
    model, params, rows = setup
    mine = model.init(jax.random.key(0), rows[:1, :8])["params"]
    assert jax.tree.map(lambda x: x.shape, mine) == \
        jax.tree.map(lambda x: x.shape, params)
    # a softmax-routed layer has no correction bias
    assert "correction_bias" not in mine["layers_0"]["moe"]
    assert sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: MODELS.build("mellum2_ep4").init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))) \
        == 595_154_176


def test_logits_of_the_whole_decoder(setup):
    model, params, rows = setup
    mine = model.apply({"params": params}, rows[:, :-1])
    theirs = ref.forward(params, rows[:, :-1], SHAPES, "f32")
    _close(mine, theirs, 2e-5)
    # the window matters at this size: with it left off the logits move
    off = ref.forward(params, rows[:, :-1], SHAPES, "f32", window_on=False)
    assert float(jnp.abs(off - theirs).max()) > 1e-2


def _program_loss(model, params, rows):
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=None, batch_stats={}, apply_fn=model.apply)
    return language.make_loss_fn(block_rows=16)(
        params, state, {"tokens": rows}, jax.random.key(0))


def test_one_head_loss_and_every_leaf_gradient(setup):
    model, params, rows = setup
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, rows), has_aux=True))(params)
    want, want_grads = train_ref_lm.loss_and_grad(
        params, rows, fam_name="mellum", shapes=SHAPES, mode="f32", rows=2)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    m = aux["metrics"]
    assert "loss_mtp" not in m and float(m["loss_main"]) == float(loss)
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(want_grads))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * max(
            float(jnp.abs(w).max()), 1e-3 * scale), jax.tree_util.keystr(path)
    assert float(jnp.abs(
        grads["layers_0"]["moe"]["router_kernel"]).max()) > 0


@pytest.mark.parametrize("kind", KINDS)
def test_attention_alone_by_layer_kind(setup, kind):
    """Window shorter than the sequence, YaRN on, 4 query heads reading 2."""
    _, params, _ = setup
    x = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.float32)
    p = params["layers_0"]["attn"]
    cfg = MODELS.build("mellum_micro").cfg
    layer = mellum.GQAttention(cfg, kind, jnp.float32)
    _close(layer.apply({"params": p}, x), ref.attention(x, p, SHAPES, kind,
                                                        "f32"), 2e-5)
    freq, factor, window = layer.rope()
    theirs = ref.inv_freq(SHAPES, kind)
    assert np.array_equal(np.asarray(freq), np.asarray(theirs[0]))
    assert factor == theirs[1]
    if kind == mellum.SLIDING:
        assert window == 8 and factor == 1.0
    else:
        # YaRN: the fastest dimension keeps its frequency, the slowest is
        # divided by the factor
        plain = 500000.0 ** (-np.arange(0, 16, 2) / 16)
        assert window is None and factor == cfg.yarn_attention_factor
        assert abs(float(freq[0]) - plain[0]) < 1e-6
        assert abs(float(freq[-1]) * 16 / plain[-1] - 1) < 1e-5


def test_yarn_frequencies_at_the_published_sizes():
    """The issue's arithmetic: low = floor(18.08), high = ceil(34.99) at
    theta 500,000, 128 dimensions, original length 8,192."""
    freq = mellum.yarn_inv_freq(500000.0, 128, 16.0, 8192, 32.0, 1.0)
    e = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    assert np.allclose(freq[:19], e[:19], rtol=1e-6)
    assert np.allclose(freq[35:], e[35:] / 16, rtol=1e-6)
    mid = (e[26] / 16) * (8 / 17) + e[26] * (9 / 17)
    assert abs(freq[26] / mid - 1) < 1e-6
    assert abs(0.1 * np.log(16) + 1 - 1.2772588722239782) < 1e-12


def test_softmax_route_against_the_reference_with_the_routers_gradient():
    p = {"router_kernel": jax.random.normal(jax.random.key(2), (64, 16))}
    x = jax.random.normal(jax.random.key(4), (50, 64))
    probs = jax.nn.softmax(x @ p["router_kernel"])
    idx, w = moe.softmax_route(probs, 4)
    theirs = ref.route(x, p, SHAPES)
    assert np.array_equal(np.asarray(idx), np.asarray(theirs[0]))
    _close(w, theirs[1], 1e-5)
    _close(jnp.sum(w, -1), jnp.ones(50), 1e-6)
    mix = jnp.cos(jnp.arange(200.0)).reshape(50, 4)

    def mine(kernel):
        return jnp.sum(moe.softmax_route(jax.nn.softmax(x @ kernel), 4)[1]
                       * mix)

    def plain(kernel):
        return jnp.sum(ref.route(x, {"router_kernel": kernel}, SHAPES)[1]
                       * mix)
    g = jax.grad(mine)(p["router_kernel"])
    _close(g, jax.grad(plain)(p["router_kernel"]), 1e-4)
    assert float(jnp.abs(g).max()) > 0


def _layer_params(key, experts, d=64, f=48, published=16):
    ks = jax.random.split(key, 4)
    return {"router_kernel": jax.random.normal(ks[0], (d, published)),
            "experts_gate": 0.1 * jax.random.normal(ks[1], (experts, d, f)),
            "experts_up": 0.1 * jax.random.normal(ks[2], (experts, d, f)),
            "experts_down": 0.1 * jax.random.normal(ks[3], (experts, f, d))}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11, 12-15 on four chips: their parts of a layer
    (there is no shared expert to count once) are the uncut layer's."""
    p = _layer_params(jax.random.key(5), 16)
    x = jax.random.normal(jax.random.key(6), (2, 24, 64), jnp.float32)
    whole, _ = ref.expert_layer(x, p, {**SHAPES, "num_experts": 16}, "f32")
    total = 0.0
    for first in range(0, 16, 4):
        share = {k: v[first:first + 4] if k.startswith("experts_") else v
                 for k, v in p.items()}
        layer = moe.HeldExpertsMlp(
            num_experts=16, held=4, first=first, top_k=4, hidden=48,
            shared_experts=0, route=moe.softmax_route, dtype=jnp.float32)
        mine = layer.apply({"params": share}, x)
        theirs, _ = ref.expert_layer(
            x, share, {**SHAPES, "first_expert": first}, "f32")
        _close(mine, theirs, 2e-5)
        total = total + mine
    _close(total, whole, 2e-5)


@pytest.mark.parametrize("window", [None, 40, 48, 16],
                         ids=["full", "w40_no_block_multiple",
                              "w48_block_multiple", "w16_one_key_block"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["h4kv4", "h8kv2"])
def test_windowed_grouped_kernels_match_the_lax_oracle(monkeypatch, window,
                                                       heads):
    """The kernels interpreted, forward, ``dq``, ``dk`` and ``dv``, blocks
    smaller than the sequence so that both ends of the key loop matter."""
    monkeypatch.setattr(flash, "CAUSAL_BLOCK_Q", 32)
    monkeypatch.setattr(flash, "CAUSAL_BLOCK_K", 16)
    h, kv = heads
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (2, h, 128, 32), jnp.float32)
    k, v = (jax.random.normal(kk, (2, kv, 128, 32), jnp.float32)
            for kk in ks[1:])

    def loss(path):
        return lambda q, k, v: jnp.sum(jnp.sin(flash.causal_attention(
            q, k, v, 32 ** -0.5, path, window)))
    for a, b in zip(jax.grad(loss("fused"), (0, 1, 2))(q, k, v),
                    jax.grad(loss("lax"), (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape
        _close(a, b, 1e-4)
    _close(flash.causal_attention(q, k, v, 0.2, "fused", window),
           flash.causal_attention(q, k, v, 0.2, "lax", window), 1e-5)


def test_the_lax_oracle_is_the_plain_masked_softmax():
    """``causal_attention_lax`` itself, grouped and windowed, against K and V
    repeated and a mask written out."""
    ks = jax.random.split(jax.random.key(14), 3)
    q = jax.random.normal(ks[0], (1, 6, 20, 8))
    k, v = (jax.random.normal(kk, (1, 2, 20, 8)) for kk in ks[1:])
    i, j = np.arange(20)[:, None], np.arange(20)[None, :]
    for window in (None, 5):
        mask = (j <= i) if window is None else (j <= i) & (j > i - window)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 3, 1)) * 0.3
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(mask, s, -jnp.inf), -1), jnp.repeat(v, 3, 1))
        _close(flash.causal_attention_lax(q, k, v, 0.3, window), want, 1e-5)
    with pytest.raises(ValueError):
        flash.flash_attention(q, k, v, window=4)          # not causal
    with pytest.raises(ValueError):
        flash.flash_attention(q[:, :5], k, v, causal=True)


def test_gmm_tiles_follow_the_products_shape():
    # GLM-4.7-Flash's products keep the tiles they were measured with
    assert moe.gmm_tiling(16384, 2048, 3072) == (512, 1024, 1024)
    # this model's 18, 14 and 7 lane tiles
    assert moe.gmm_tiling(65536, 2304, 1792) == (512, 768, 896)
    assert moe.gmm_tiling(65536, 896, 2304) == (512, 896, 768)
    assert moe.buffer_capacity(2 * 8192 * 8, 16, 64) == 65536
    assert moe.grouped_route(65536) == "ragged_dot"          # this is a CPU


@pytest.fixture(scope="module")
def micro_run(tmp_path_factory):
    return _step_scopes.two_steps("mellum_micro",
                                  tmp_path_factory.mktemp("mellum_micro"))


def test_two_steps_through_build_trainer_with_the_one_head_loss(micro_run):
    import train as train_cli
    assert train_cli.model_task("mellum_micro") == "language"
    assert train_cli.model_task("mellum2_ep4") == "language"
    trainer, seen = micro_run.trainer, micro_run.seen
    assert len(seen) == 2 and int(trainer.state.step) == 2
    assert all(np.isfinite(m["loss"]) and 5.5 < m["loss_main"] < 7
               and "loss_mtp" not in m and m["loss"] == m["loss_main"]
               for m in seen)
    for layer in ("layers_0", "layers_3"):
        assert seen[0][f"moe/rows_held/{layer}"] \
            + seen[0][f"moe/rows_absent/{layer}"] == 8 * 32 * 4
        assert seen[0][f"moe/buffer_rows/{layer}"] in (8 * 32 * 2, 8 * 32 * 4)
    events = [e for e in micro_run.kernels
              if e.get("name") == "gqa_attention"]
    # one tally a kind, by window; the sliding one names three blocks
    by_window = {e["window"]: e for e in events if e["shape"][3] == 32}
    assert set(by_window) == {8, None}
    assert all(e["path"] == "lax" and e["shape"] == [8, 4, 2, 32, 16]
               and e["forward_kept"] is False for e in by_window.values())
    assert {m.split("/")[0] for m in by_window[8]["members"]} == {
        "layers_0", "layers_1", "layers_2"}
    assert {m.split("/")[0] for m in by_window[None]["members"]} == {
        "layers_3"}
    assert "loss_sum" in trainer.evaluate()


def test_every_scope_of_the_vocabulary_names_ops_of_the_step(micro_run):
    _step_scopes.check_decoder_vocabulary(micro_run.paths)


def test_glue_stays_outside_the_cores_and_little_is_unplaced(micro_run):
    _step_scopes.check_glue_and_unplaced(micro_run.paths, ("sliding_core", "full_core"))


@pytest.mark.parametrize("path", ["lax", "fused"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_block_under_the_policy_is_the_block(setup, monkeypatch, kind, path):
    """``decoder.remat_block``'s block against the un-remat'd one on the same
    weights, a sliding and a full layer: the same loss and leaf gradients; on
    the fused path (kernels interpreted) its gradient holds the un-remat'd
    block's three kernels and not a fourth, and its flight tally reads
    ``forward_kept``."""
    if path == "fused":
        monkeypatch.setattr(
            flash, "select_path", lambda tokens, width, initializing=False:
            "lax" if initializing else "fused")
    _, params, _ = setup
    cfg = MODELS.build("mellum_micro").cfg
    p = params["layers_0" if kind == mellum.SLIDING else "layers_3"]
    x = jax.random.normal(jax.random.key(21), (2, 32, 64), jnp.float32)
    recorder = flight.get_recorder()
    results = {}
    for name, cls in (("kept", mellum._RematBlock),
                      ("whole", mellum.MellumBlock)):
        recorder.clear()
        block = cls(cfg, kind, jnp.float32)
        grad = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            block.apply({"params": p}, x))), (0, 1))
        calls = sum(e.primitive.name == "pallas_call" for e in
                    audit.iter_eqns(jax.make_jaxpr(grad)(p, x)))
        assert calls == (3 if path == "fused" else 0), (name, calls)
        results[name] = grad(p, x)
        tally, = [e for e in recorder.events("kernel")
                  if e.get("name") == "gqa_attention"]
        assert tally["path"] == path
        assert tally["window"] == (8 if kind == mellum.SLIDING else None)
        assert tally["forward_kept"] is (name == "kept" and path == "fused")
    assert float(results["kept"][0]) == float(results["whole"][0])
    for kept, whole in zip(*(jax.tree.leaves(results[n][1])
                             for n in ("kept", "whole"))):
        _close(kept, whole, 1e-6)


def test_flops_functions_against_a_count_of_the_references_products(
        monkeypatch):
    """Every matrix product of the reference goes through ``ops.einsum``:
    count their multiply-accumulates. With every published expert held and
    chosen (so that the plain loop over experts does the required rows) the
    count is the FLOPs function's, but for the attention scores, which the
    reference takes over every (query, key) pair and masks: those are counted
    apart, a layer kind at a time, from the mask itself."""
    shapes = {**SHAPES, "num_experts": 4, "num_experts_published": 4,
              "seq_len": 16}
    macs = []
    real = ref_ops.einsum

    def counting(spec, a, b, mode):
        if "q" in spec.split("->")[0]:          # QK^T, PV: by the mask below
            return real(spec, a, b, mode)
        ins, _ = spec.replace("...", "").split("->")
        sizes, lead = {}, 1
        for names, x in zip(ins.split(","), (a, b)):
            sizes.update(zip(names[::-1], x.shape[::-1]))
            lead *= int(np.prod(x.shape[: x.ndim - len(names)]))
        macs.append(int(np.prod(list(sizes.values()))) * lead)
        return real(spec, a, b, mode)

    def unrolled(body, carry, xs):
        for i in range(len(jax.tree.leaves(xs)[0])):
            carry, _ = body(carry, jax.tree.map(lambda a: a[i], xs))
        return carry, None
    monkeypatch.setattr(ref_ops, "einsum", counting)
    monkeypatch.setattr(jax.lax, "scan", unrolled)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    params = ref.make_params(ref.param_spec(shapes), 0)
    ref.forward(params, jnp.zeros((1, 16), jnp.int32), shapes, "f32",
                remat=False)
    i, j = np.arange(16)[:, None], np.arange(16)[None, :]
    seen = {mellum.SLIDING: int(np.sum((j <= i) & (j > i - 8))),
            mellum.FULL: int(np.sum(j <= i))}
    scores = sum(seen[kind] * 4 * 2 * 16 for kind in ref.layer_kinds(shapes))
    for kind in KINDS:
        assert flops.pairs(shapes, kind) == seen[kind]
    assert sum(macs) + scores == flops.forward_macs(shapes)
    assert flops.train_flops(shapes) == 6 * flops.forward_macs(shapes)


def test_flops_and_parameters_of_the_cell_are_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "mellum2_ep4.json")))
    s = cfg["shapes"]
    assert flops.pairs(s, mellum.SLIDING) == 7_864_832
    assert flops.pairs(s, mellum.FULL) == 33_558_528
    assert abs(flops.forward_macs(s) / 1e12 - 2.04) < 0.005
    assert abs(2 * flops.train_flops(s) / 1e12 - 24.46) < 0.01   # a step
    assert flops.expected_rows_per_token(s) == 2.0
    # least times of the two cores a step of two rows at 197 TFLOP/s
    for fn, ms in ((flops.sliding_attention_work, 11.8),
                   (flops.full_attention_work, 16.7)):
        work = fn(s, 2)
        assert abs(work["flops"] / 197e12 * 1e3 - ms) < 0.06
        assert work["bytes"] / 819e9 < work["flops"] / 197e12
    assert sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(
        ref.param_spec(s), is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))) == 595_154_176
    # the file holds every number of the published config under its key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = next(json.loads(line) for line in open(catalog)
               if '"Mellum2-12B-A2.5B-Instruct"' in line) \
        if os.path.exists(catalog) else None
    if row is not None:
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_experts", "vocab_size"]
    assert cfg["layer_types"] == s["layer_types"].split(",")
    yarn = cfg["rope_parameters"]["full_attention"]
    for key, value in s.items():
        if key.startswith("yarn_"):
            assert yarn[key[5:]] == value, key
        elif key in cfg and not isinstance(value, (bool, str)):
            assert cfg[key] == value, key
    # the program's entry is the same cut
    mine = MODELS.build("mellum2_ep4").cfg
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window",
                "moe_intermediate_size", "num_experts_per_tok", "vocab_size",
                "rms_norm_eps", "rope_theta", "yarn_factor",
                "yarn_attention_factor"):
        assert getattr(mine, key) == s[key], key
    assert (mine.num_experts, mine.experts_held) == (64, 16)
    assert [mine.kind(i) for i in range(4)] == cfg["layer_types"]
