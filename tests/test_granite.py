"""Granite 4.0-H's decoder (``models/language/granite.py``: Mamba-2 mixers
with a chunked scan, a NoPE grouped-query attention layer among them, a tied
head under μP multipliers), the scan, the convolution and the gated norm of
``ops/ssd.py``, at a small size on the CPU with seeded weights, against the
benchmark's plain reference (``benchmarks/references/granite.py``), which
imports nothing of the program and runs the recurrence one position at a
time."""
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
from benchmarks.flops import granite as flops                 # noqa: E402
from benchmarks.references import granite as ref              # noqa: E402
from benchmarks.references import ops as ref_ops              # noqa: E402
from benchmarks.references import train_ref_lm                # noqa: E402
from deeplearning_tpu.core.registry import MODELS             # noqa: E402
from deeplearning_tpu.models.language import granite          # noqa: E402
from deeplearning_tpu.ops import ssd                          # noqa: E402
from deeplearning_tpu.train import language                   # noqa: E402
from deeplearning_tpu.train.state import TrainState           # noqa: E402

# the registry's granite_micro, as the reference's shapes
SHAPES = json.load(open(os.path.join(
    ROOT, "benchmarks", "tests", "configs", "granite_micro.json")))["shapes"]
SHAPES = {**SHAPES, "seq_len": 32}
SEED = 13


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-12), (
        np.abs(a - b).max(), np.abs(b).max())


def plain_recurrence(x, dt, a, b, c, d):
    """``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t``,
    a position at a time."""
    heads_a_group = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(t, heads_a_group, axis=2) for t in (b, c))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhps,bhs->bhp", state, c_t)
    state = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, y = jax.lax.scan(step, state, tuple(jnp.moveaxis(t, 1, 0)
                                           for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def _scan_inputs(groups, n=40, h=4, p=8, s=6):
    ks = jax.random.split(jax.random.key(groups), 6)
    return (jax.random.normal(ks[0], (2, n, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, n, h)) - 1.0),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (2, n, groups, s)),
            jax.random.normal(ks[4], (2, n, groups, s)),
            jax.random.normal(ks[5], (h,)))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [8, 16], ids=["chunk8", "chunk16"])
def test_chunked_scan_is_the_recurrence(chunk, groups):
    """Forward and the gradients of x, Δ, A, B, C and D, 40 positions (the
    last chunk short), float32: the two differ by the order of their sums
    and by exp of a difference of cumulative sums against a product of
    exps, 2e-5 of the largest output and 5e-5 of the largest gradient."""
    args = _scan_inputs(groups)

    def chunked(x, dt, a, b, c, d):
        return ssd.ssd_scan(x, dt, a, b, c, chunk) + d[:, None] * x
    _close(jax.jit(chunked)(*args), jax.jit(plain_recurrence)(*args), 2e-5)

    def grad(fn):
        return jax.jit(jax.grad(lambda *t: jnp.sum(jnp.sin(fn(*t))),
                                argnums=range(6)))
    mine, theirs = grad(chunked)(*args), grad(plain_recurrence)(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "d"), mine, theirs):
        assert g.shape == w.shape, name
        _close(g, w, 5e-5)
    # the state crosses chunks: each chunk scanned alone, from zero, is
    # another result
    x, dt, a, b, c, _ = args
    scan = jax.jit(ssd.ssd_scan, static_argnums=5)
    alone = jnp.concatenate([scan(
        x[:, i:i + chunk], dt[:, i:i + chunk], a, b[:, i:i + chunk],
        c[:, i:i + chunk], chunk) for i in range(0, 40, chunk)], axis=1)
    whole = scan(x, dt, a, b, c, chunk)
    assert float(jnp.abs(alone - whole).max()) > 0.1 * float(
        jnp.abs(whole).max())


def test_convolution_and_gated_norm_against_loops():
    x = jax.random.normal(jax.random.key(3), (2, 11, 5))
    kernel = jax.random.normal(jax.random.key(4), (4, 5))
    bias = jax.random.normal(jax.random.key(5), (5,))
    want = np.zeros((2, 11, 5), np.float32) + np.asarray(bias)
    for t in range(11):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(kernel[i] * x[:, t - 3 + i])
    _close(ssd.causal_conv1d(x, kernel, bias), want, 1e-6)
    _close(ssd.causal_conv1d(x, kernel, bias),
           ref.short_conv(x, kernel, bias), 1e-6)
    y, z = x, jax.random.normal(jax.random.key(6), (2, 11, 5))
    g = np.asarray(y) * np.asarray(jax.nn.silu(z))
    want = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5) * 1.5
    _close(ssd.gated_rms_norm(y, z, 1.5, 1e-5), want, 1e-5)


@pytest.fixture(scope="module")
def setup():
    model = MODELS.build("granite_micro", dtype=jnp.float32)
    params = ref.make_params(ref.param_spec(SHAPES), SEED)
    rows = jnp.asarray(np.random.default_rng(SEED).integers(
        0, SHAPES["vocab_size"], (4, SHAPES["seq_len"] + 1)), jnp.int32)
    return model, params, rows


@pytest.fixture
def small_pieces(monkeypatch):
    """The reference's segments, stretches and query blocks shorter than a
    test's row, so that every carry between them is exercised."""
    monkeypatch.setattr(ref, "SEGMENT", 16)
    monkeypatch.setattr(ref, "STRETCH", 4)
    monkeypatch.setattr(ref, "ATTENTION_ROWS", 8)


def test_program_and_reference_share_one_parameter_tree(setup):
    model, params, rows = setup
    mine = model.init(jax.random.key(0), rows[:1, :8])["params"]
    assert jax.tree.map(lambda x: x.shape, mine) == \
        jax.tree.map(lambda x: x.shape, params)
    # tied: no head of its own
    assert set(mine) == {"embed", "norm"} | {f"layers_{i}" for i in range(4)}
    assert model.cfg.layer_types == (granite.MAMBA, granite.MAMBA,
                                     granite.ATTENTION, granite.MAMBA)
    assert "attn" in mine["layers_2"] and "mixer" in mine["layers_3"]
    full = jax.eval_shape(lambda: MODELS.build("granite4_h_micro_vocab8").init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree.leaves(full)) == 772_160_448


def test_logits_of_the_whole_decoder_and_the_planted_fault(setup,
                                                           small_pieces):
    """float32 on both sides: the program's chunked scan and the reference's
    recurrence differ by the order of sums, 2e-5 of the largest logit. The
    state left behind at each chunk of 8 moves the logits by some 1e-4 of
    their largest at this size (the mixers are 0.22 of a small share of the
    residual stream): the fault parts, by five times the tolerance at
    least."""
    model, params, rows = setup
    mine = model.apply({"params": params}, rows[:, :-1])
    theirs = ref.forward(params, rows[:, :-1], SHAPES, "f32")
    _close(mine, theirs, 2e-5)
    dropped = ref.forward(params, rows[:, :-1], SHAPES, "f32", carried=False)
    assert float(jnp.abs(dropped - theirs).max()) > 1e-4 * float(
        jnp.abs(theirs).max())


def _program_loss(model, params, rows):
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=None, batch_stats={}, apply_fn=model.apply)
    return language.make_loss_fn(block_rows=16)(
        params, state, {"tokens": rows}, jax.random.key(0))


def test_loss_and_every_leaf_gradient(setup, small_pieces):
    """The one-head loss through ``train/language.py`` (the tied head and its
    divisor from ``CausalLM.head``) against the reference's; every leaf's
    gradient to 2e-4 of its largest entry (float32 on both sides, measured
    2e-6) or of a thousandth of the largest gradient anywhere: the leaves
    reached only through the recurrence (``A_log``, ``dt_bias``) are
    smaller than the rest by four orders."""
    model, params, rows = setup
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, rows), has_aux=True))(params)
    want, want_grads = train_ref_lm.loss_and_grad(
        params, rows, fam_name="granite", shapes=SHAPES, mode="f32", rows=2)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert "loss_mtp" not in aux["metrics"]
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(want_grads))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * max(
            float(jnp.abs(w).max()), 1e-3 * scale), jax.tree_util.keystr(path)
    for leaf in ("A_log", "dt_bias", "conv_kernel", "D"):
        assert float(jnp.abs(grads["layers_0"]["mixer"][leaf]).max()) > 0


@pytest.mark.parametrize("entry", ["granite_micro", "mellum_micro"])
def test_the_head_is_the_models_to_say(entry):
    """``CausalLM.head``: tied, the embedding transposed and the divisor of
    the logits; untied, the head's own kernel and 1. The blocked loss with
    that divisor is the plain cross entropy of the divided logits."""
    model = MODELS.build(entry, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 17)),
                         jnp.int32)
    variables = model.init(jax.random.key(1), tokens[:, :-1])
    kernel, scaling = model.apply(variables, method="head")
    p = variables["params"]
    if entry == "granite_micro":
        assert scaling == 8.0 and "head" not in p
        assert np.array_equal(kernel, p["embed"]["embedding"].T)
    else:
        assert scaling == 1.0
        assert np.array_equal(kernel, p["head"]["kernel"])
    hidden = model.apply(variables, tokens[:, :-1], return_hidden=True)[0]
    logits = model.apply(variables, tokens[:, :-1])
    _close(logits, hidden @ kernel / scaling, 1e-5)
    flat, targets = hidden.reshape(-1, hidden.shape[-1]), tokens[:, 1:]
    loss_sum, hits = language.blocked_cross_entropy(
        flat, kernel, targets.reshape(-1), jnp.ones(32), 8, scaling)
    plain = jax.nn.log_softmax(logits.reshape(32, -1), -1)
    want = -jnp.sum(jnp.take_along_axis(plain, targets.reshape(-1, 1), -1))
    assert abs(float(loss_sum) - float(want)) < 1e-5 * float(want)
    assert float(hits) == float(jnp.sum(
        jnp.argmax(plain, -1) == targets.reshape(-1)))


@pytest.fixture(scope="module")
def micro_run(tmp_path_factory):
    return _step_scopes.two_steps("granite_micro",
                                  tmp_path_factory.mktemp("granite_micro"))


def test_two_steps_through_build_trainer(micro_run):
    import train as train_cli
    assert train_cli.model_task("granite4_h_micro_vocab8") == "language"
    trainer, seen = micro_run.trainer, micro_run.seen
    assert len(seen) == 2 and int(trainer.state.step) == 2
    assert all(np.isfinite(m["loss"]) and 5.5 < m["loss_main"] < 7
               and m["loss"] == m["loss_main"] for m in seen)
    scans = [e for e in micro_run.kernels if e.get("name") == "ssd_scan"
             and e["shape"][1] == 32]
    assert len(scans) == 1 and scans[0]["path"] == "lax"
    assert scans[0]["shape"] == [8, 32, 4, 16, 16, 8]
    assert {m.split("/")[0] for m in scans[0]["members"]} == {
        "layers_0", "layers_1", "layers_3"}
    attn, = [e for e in micro_run.kernels if e.get("name") == "gqa_attention"
             and e["shape"][3] == 32]
    assert attn["path"] == "lax" and attn["window"] is None
    assert attn["forward_kept"] is False and attn["shape"] == [8, 4, 2, 32, 16]
    assert {m.split("/")[0] for m in attn["members"]} == {"layers_2"}
    assert "loss_sum" in trainer.evaluate()


def test_every_op_of_the_step_is_placed_by_a_name(micro_run):
    """The step's vocabulary with no rotary embedding (NoPE), the mixer's
    three scopes forward and backward, the projections outside them, and
    little unplaced."""
    paths = micro_run.paths
    _step_scopes.check_decoder_vocabulary(paths, rotary=False)
    _step_scopes.check_glue_and_unplaced(paths, ("full_core",))
    for scope in ("ssd_scan", "short_conv", "gated_norm"):
        forward, backward = _step_scopes.under(paths, scope)
        assert forward and backward, scope
        assert not [p for p in forward + backward
                    if "/in_proj/" in p or "/out_proj/" in p], scope
    import re
    for metric in ("ssd_scan_roofline", "full_attention_roofline.granite",
                   "mamba_glue_ms"):
        rx = re.compile(_step_scopes.metric_params(metric)["pattern"])
        assert any(rx.search(p) for p in paths), metric


def test_flops_functions_against_a_count_of_the_references_products(
        monkeypatch):
    """Every matrix product of the reference goes through ``ops.einsum``:
    count their multiply-accumulates, but for the attention scores (taken
    over every (query, key) pair and masked: counted from the mask) and the
    recurrence's ``S_t C_t`` a position at a time (the chunked form's work is
    ``scan_macs``, checked against the cell's arithmetic below)."""
    shapes = {**SHAPES, "seq_len": 16}
    macs = []
    real = ref_ops.einsum

    def counting(spec, a, b, mode):
        ins = spec.replace("...", "").split("->")[0]
        if "q" in ins or "bgkps" in ins:
            return real(spec, a, b, mode)
        sizes, lead = {}, 1
        for names, x in zip(ins.split(","), (a, b)):
            sizes.update(zip(names[::-1], x.shape[::-1]))
            lead *= int(np.prod(x.shape[: x.ndim - len(names)]))
        macs.append(int(np.prod(list(sizes.values()))) * lead)
        return real(spec, a, b, mode)

    def unrolled(body, carry, xs):
        ys = []
        for i in range(len(jax.tree.leaves(xs)[0])):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        return carry, (None if ys[0] is None
                       else jax.tree.map(lambda *t: jnp.stack(t), *ys))
    monkeypatch.setattr(ref_ops, "einsum", counting)
    monkeypatch.setattr(jax.lax, "scan", unrolled)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    params = ref.make_params(ref.param_spec(shapes), 0)
    ref.forward(params, jnp.zeros((1, 16), jnp.int32), shapes, "f32",
                remat=False)
    causal = 16 * 17 // 2
    assert flops.pairs(shapes) == causal
    scores = causal * 4 * 2 * 16
    assert sum(macs) + scores + 3 * flops.scan_macs(shapes) \
        == flops.forward_macs(shapes)
    assert flops.train_flops(shapes) == 6 * flops.forward_macs(shapes)


def test_flops_and_parameters_of_the_cell():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "granite4_h_micro_vocab8.json")))
    s = cfg["shapes"]
    # a sequence's forward work: 6.11 T of projections and MLPs, 0.137 T of
    # attention, 0.117 T of scans at chunk 256, 0.21 T of head
    assert abs(flops.forward_macs(s) / 1e12 - 6.58) < 0.005
    assert abs(9 * flops.scan_macs(s) / 1e12 - 0.117) < 0.0005
    assert abs(flops.train_flops(s) / 1e12 - 39.5) < 0.05      # a step
    # the scans' least time a step at 197 TFLOP/s and 819 GB/s: 3.6 ms by
    # operations, 3.9 ms by bytes
    work = flops.ssd_scan_work(s, 1)
    assert abs(work["flops"] / 197e12 * 1e3 - 3.57) < 0.01
    assert abs(work["bytes"] / 819e9 * 1e3 - 3.90) < 0.01
    attn = flops.full_attention_work(s, 1)
    assert attn["bytes"] / 819e9 < attn["flops"] / 197e12
    assert sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(
        ref.param_spec(s), is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[1], str))) == 772_160_448
    # the cut: depth, its period and the vocabulary, each beside its
    # published value
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "vocab_size"] == sorted(cfg["published"],
                                                      key=cfg["reduced"].index)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["vocab_size"]) == (40, 8 * 12544)
    assert cfg["layer_types"] == s["layer_types"].split(",")
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:10]
    mine = MODELS.build("granite4_h_micro_vocab8").cfg
    for key, value in s.items():
        if hasattr(mine, key) and key != "layer_types":
            assert getattr(mine, key) == value, key
    assert [mine.kind(i) for i in range(10)] == cfg["layer_types"]
    assert mine.intermediate_size == cfg["shared_intermediate_size"]
    assert mine.mamba_n_heads * mine.mamba_d_head \
        == cfg["mamba_expand"] * cfg["hidden_size"]
    assert mine.tie_word_embeddings is cfg["tie_word_embeddings"] is True
