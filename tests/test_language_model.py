"""GLM-4.7-Flash's decoder (``models/language/glm_moe_lite.py``), its chip's
share of the expert layer (``parallel/moe.py::HeldExpertsMlp``), the causal
attention path and the language-model task, at a small size on the CPU with
seeded weights, against the benchmark's plain reference
(``benchmarks/references/glm_moe_lite.py``), which imports nothing of the
program; and every language family's rehearsal of the benchmark's token
driver (``test_token_driver_rehearsal_and_its_controls``)."""
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

import _step_scopes                                          # noqa: E402
from benchmarks.flops import glm_moe_lite as flops           # noqa: E402
from benchmarks.references import glm_moe_lite as ref        # noqa: E402
from benchmarks.references import ops as ref_ops             # noqa: E402
from benchmarks.references import train_ref_lm               # noqa: E402
from deeplearning_tpu.analysis import jaxpr as audit         # noqa: E402
from deeplearning_tpu.core.registry import MODELS            # noqa: E402
from deeplearning_tpu.models.language import glm_moe_lite as glm  # noqa: E402
from deeplearning_tpu.obs import flight                      # noqa: E402
from deeplearning_tpu.ops.pallas import flash_attention as flash  # noqa: E402
from deeplearning_tpu.parallel import moe                    # noqa: E402
from deeplearning_tpu.train import language                  # noqa: E402
from deeplearning_tpu.train.state import TrainState          # noqa: E402

# the registry's glm_moe_lite_micro, as the reference's shapes
SHAPES = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=160, moe_intermediate_size=48,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=4,
    n_routed_experts_published=16, first_expert=0, num_experts_per_tok=4,
    n_shared_experts=1, routed_scaling_factor=1.8, rope_theta=1e6,
    rms_norm_eps=1e-5, num_nextn_predict_layers=1, seq_len=32,
    mtp_loss_weight=0.3)
SEED = 11


@pytest.fixture(scope="module")
def setup():
    model = MODELS.build("glm_moe_lite_micro", dtype=jnp.float32)
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
    assert sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: MODELS.build("glm47_flash_ep8").init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))) \
        == 706_518_848


@pytest.mark.parametrize("head", [0, 1], ids=["main", "mtp"])
def test_logits_of_the_whole_decoder(setup, head):
    model, params, rows = setup
    mine = model.apply({"params": params}, rows[:, :-1],
                       next_tokens=rows[:, 1:])
    theirs = ref.forward(params, rows[:, :-1], rows[:, 1:], SHAPES, "f32")
    _close(mine[head], theirs[head], 2e-5)


def _program_loss(model, params, rows):
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=None, batch_stats={}, apply_fn=model.apply)
    return language.make_loss_fn(block_rows=16)(
        params, state, {"tokens": rows}, jax.random.key(0))


def test_loss_with_the_mtp_term_and_every_leaf_gradient(setup):
    model, params, rows = setup
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, rows), has_aux=True))(params)
    want, want_grads = train_ref_lm.loss_and_grad(
        params, rows, fam_name="glm_moe_lite", shapes=SHAPES, mode="f32",
        rows=2)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    m = aux["metrics"]
    assert abs(float(m["loss_main"] + 0.3 * m["loss_mtp"]) - float(loss)) < 1e-5
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(want_grads))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * max(
            float(jnp.abs(w).max()), 1e-3 * scale), jax.tree_util.keystr(path)
    # no gradient reaches the correction bias
    assert not np.any(np.asarray(grads["layers_1"]["moe"]["correction_bias"]))


def test_mla_alone_rotary_included(setup):
    _, params, _ = setup
    x = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.float32)
    p = params["layers_1"]["attn"]
    cfg = MODELS.build("glm_moe_lite_micro").cfg
    mine = glm.MLA(cfg, jnp.float32).apply({"params": p}, x)
    _close(mine, ref.mla(x, p, SHAPES, "f32"), 2e-5)
    # the rotation is by position and leaves a row's norm alone
    r = glm.rotary(x[..., :8], 1e6)
    _close(r[:, 0], x[:, 0, :8], 1e-6)
    _close(jnp.linalg.norm(r, axis=-1), jnp.linalg.norm(x[..., :8], axis=-1),
           1e-5)
    assert float(jnp.abs(r[:, 5] - x[:, 5, :8]).max()) > 1e-2


def test_router_bias_in_the_choice_but_not_in_the_weights():
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.2, 0.1, 0.05]])
    bias = jnp.asarray([0.0, 0.0, -0.6, 0.0, 0.55, 0.0])
    idx, w = moe.sigmoid_route(scores, bias, 3, 1.8)
    # 0.7 - 0.6 loses its place to 0.1 + 0.55
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 4]
    chosen = np.asarray([0.9, 0.8, 0.1])
    want = dict(zip([0, 1, 4], 1.8 * chosen / chosen.sum()))
    for e, got in zip(np.asarray(idx[0]), np.asarray(w[0])):
        assert abs(got - want[int(e)]) < 1e-6
    assert abs(float(w.sum()) - 1.8) < 1e-6
    # the reference's router on random scores
    p = {"router_kernel": jax.random.normal(jax.random.key(2), (64, 16)),
         "correction_bias": 0.3 * jax.random.normal(jax.random.key(3), (16,))}
    x = jax.random.normal(jax.random.key(4), (50, 64))
    s = jax.nn.sigmoid(x @ p["router_kernel"])
    mine = moe.sigmoid_route(s, p["correction_bias"], 4, 1.8)
    theirs = ref.route(x, p, SHAPES)
    assert np.array_equal(np.asarray(mine[0]), np.asarray(theirs[0]))
    _close(mine[1], theirs[1], 1e-5)
    assert not np.array_equal(np.asarray(theirs[0]), np.asarray(
        ref.route(x, p, SHAPES, bias_in_choice=False)[0]))


def _layer_params(key, experts, d=64, f=48, published=16):
    ks = jax.random.split(key, 8)
    swiglu = lambda k: {n: {"kernel": 0.1 * jax.random.normal(kk, shape)}
                        for n, kk, shape in zip(
                            ("gate", "up", "down"), jax.random.split(k, 3),
                            ((d, f), (d, f), (f, d)))}
    return {"router_kernel": jax.random.normal(ks[0], (d, published)),
            "correction_bias": 0.2 * jax.random.normal(ks[1], (published,)),
            "experts_gate": 0.1 * jax.random.normal(ks[2], (experts, d, f)),
            "experts_up": 0.1 * jax.random.normal(ks[3], (experts, d, f)),
            "experts_down": 0.1 * jax.random.normal(ks[4], (experts, f, d)),
            "shared": swiglu(ks[5])}


def _share(p, first, held):
    cut = {k: v[first:first + held] if k.startswith("experts_") else v
           for k, v in p.items()}
    return cut


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips' routed parts plus the shared expert once are what the
    uncut 16-expert layer gives."""
    p = _layer_params(jax.random.key(5), 16)
    x = jax.random.normal(jax.random.key(6), (2, 24, 64), jnp.float32)
    whole, _ = ref.expert_layer(x, p, {**SHAPES, "n_routed_experts": 16}, "f32")
    shared = ref.swiglu(x, p["shared"], "f32")
    routed = 0.0
    for first in range(0, 16, 4):
        layer = moe.HeldExpertsMlp(num_experts=16, held=4, first=first,
                                   top_k=4, hidden=48, dtype=jnp.float32)
        mine = layer.apply({"params": _share(p, first, 4)}, x)
        theirs, _ = ref.expert_layer(
            x, _share(p, first, 4),
            {**SHAPES, "n_routed_experts": 4, "first_expert": first}, "f32")
        _close(mine, theirs, 2e-5)
        routed = routed + (mine - shared)
    _close(routed + shared, whole, 2e-5)


def test_no_row_is_dropped_when_every_token_picks_the_same_experts():
    """Every choice of every token lands on this chip (the worst case the
    row buffer is sized for): all ``top_k`` x tokens rows are computed."""
    p = _layer_params(jax.random.key(7), 4)
    p["correction_bias"] = p["correction_bias"].at[:4].add(10.0)
    x = jax.random.normal(jax.random.key(8), (2, 16, 64), jnp.float32)
    layer = moe.HeldExpertsMlp(num_experts=16, held=4, top_k=4, hidden=48,
                               dtype=jnp.float32)
    mine, sown = layer.apply({"params": p}, x, mutable=["moe_metrics"])
    theirs, idx = ref.expert_layer(x, p, SHAPES, "f32")
    assert set(np.asarray(idx).ravel().tolist()) == {0, 1, 2, 3}
    _close(mine, theirs, 2e-5)
    counters = sown["moe_metrics"]
    assert int(counters["rows_held"][0]) == 4 * 32
    assert int(counters["rows_absent"][0]) == 0
    assert float(counters["load_max_over_mean"][0]) == 1.0
    # and with one expert taking every token's first choice, the others none
    g = jax.grad(lambda x: layer.apply({"params": p}, x).sum())(x)
    assert np.all(np.isfinite(np.asarray(g)))


def _steered(held_tokens, single_tokens, tokens=512):
    """A 16-expert layer's parameters and input in which ``held_tokens``
    tokens send all four choices to held experts 0-3, ``single_tokens`` one
    choice (to expert 1) and the others none: 4 x held_tokens + single_tokens
    rows present."""
    p = _layer_params(jax.random.key(21), 4)
    steer = np.zeros((64, 16), np.float32)
    steer[0, 0:4] = steer[1, 4:8] = steer[2, [1, 8, 9, 10]] = 1.0
    p["router_kernel"] = 0.05 * p["router_kernel"] + steer
    p["correction_bias"] = 0.1 * p["correction_bias"]
    kind = np.ones(tokens, np.int32)
    kind[:held_tokens] = 0
    kind[held_tokens:held_tokens + single_tokens] = 2
    kind = np.random.default_rng(3).permutation(kind)
    x = 0.1 * jax.random.normal(jax.random.key(22), (2, tokens // 2, 64))
    x = x + 2.0 * jax.nn.one_hot(kind, 64).reshape(x.shape)
    return p, x


def _mix(x):
    return jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)


def _layer_value_and_grad(p, x, held=4):
    layer = moe.HeldExpertsMlp(num_experts=16, held=held, top_k=4, hidden=48,
                               dtype=jnp.float32)
    mix = _mix(x)

    def loss(p, x):
        y, sown = layer.apply({"params": p}, x, mutable=["moe_metrics"])
        return jnp.sum(y * mix), (y, sown["moe_metrics"])
    return jax.value_and_grad(loss, (0, 1), has_aux=True), p, x


# 512 tokens x 4 choices over 16 experts of which 4 are held: a row buffer
# of 1,024 rows for 2,048 choices
@pytest.mark.parametrize("held_tokens,single_tokens,buffer_rows", [
    (0, 0, 1024), (100, 3, 1024), (256, 0, 1024), (256, 1, 2048),
    (512, 0, 2048)], ids=["none", "some", "exactly_c", "c_plus_1", "all"])
def test_row_buffer_equals_the_reference_and_the_full_buffer(
        monkeypatch, held_tokens, single_tokens, buffer_rows):
    """Value and every gradient, whatever share of the rows is present,
    against the plain reference and against a buffer of every choice; one
    row past the buffer and the layer makes a second pass: no row is
    dropped."""
    assert moe.buffer_capacity(2048, 4, 16) == 1024
    fn, p, x = _layer_value_and_grad(*_steered(held_tokens, single_tokens))
    (_, (y, counters)), grads = fn(p, x)
    assert int(counters["rows_held"][0]) == 4 * held_tokens + single_tokens
    assert int(counters["buffer_rows"][0]) == buffer_rows
    mix = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    def reference(p, x):
        y = ref.expert_layer(x, p, SHAPES, "f32")[0]
        return jnp.sum(y * mix), y
    theirs = jax.value_and_grad(reference, (0, 1), has_aux=True)(p, x)
    _close(y, theirs[0][1], 2e-5)
    monkeypatch.setattr(moe, "buffer_capacity", lambda choices, *_: choices)
    (_, (y_full, counters)), grads_full = fn(p, x)
    assert int(counters["buffer_rows"][0]) == 2048
    _close(y, y_full, 1e-6)
    for mine, full, ref_grad in zip(*map(jax.tree.leaves,
                                         (grads, grads_full, theirs[1]))):
        _close(mine, full, 1e-6)
        _close(mine, ref_grad, 2e-5)
    assert float(jnp.abs(grads[0]["experts_down"]).max()) > 0 \
        or not held_tokens + single_tokens


def test_no_full_size_rows_and_no_scatter():
    """A share of 1/8, value and gradient: nothing as large as tokens x top_k
    rows at the model's width stands in either direction, one pass or
    several (the token side sums top_k gathers of one row a token), and
    nothing scatters."""
    fn, p, x = _layer_value_and_grad(*_steered(40, 5), held=2)
    p = {k: v[:2] if k.startswith("experts_") else v for k, v in p.items()}
    traced = jax.make_jaxpr(fn)(p, x)
    choices, d = 512 * 4, 64
    assert moe.buffer_capacity(choices, 2, 16) == 512
    conds = [e for e in audit.iter_eqns(traced) if e.primitive.name == "cond"]
    assert len(conds) == 2                       # forward, backward
    assert max(a.size for a in audit.iter_avals(traced) if a.shape) \
        < choices * d
    assert not [e for e in audit.iter_eqns(traced)
                if "scatter" in e.primitive.name]


def test_blocked_loss_equals_the_whole_one():
    h = jax.random.normal(jax.random.key(9), (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.key(10), (32, 50), jnp.float32)
    t = jax.random.randint(jax.random.key(11), (64,), 0, 50)
    weights = (jnp.arange(64) % 7 != 0).astype(jnp.float32)

    def whole(h, w):
        logp = jax.nn.log_softmax(h @ w)
        return -jnp.sum(jnp.take_along_axis(logp, t[:, None], 1)[:, 0] * weights)

    def blocked(h, w):
        return language.blocked_cross_entropy(h, w, t, weights, 16)[0]
    _close(blocked(h, w), whole(h, w), 1e-6)
    for a, b in zip(jax.grad(blocked, (0, 1))(h, w),
                    jax.grad(whole, (0, 1))(h, w)):
        _close(a, b, 1e-5)
    hits = language.blocked_cross_entropy(h, w, t, weights, 16)[1]
    assert float(hits) == float(jnp.sum((jnp.argmax(h @ w, -1) == t) * weights))


def test_causal_attention_fused_path_matches_the_lax_oracle(monkeypatch):
    """The kernels interpreted, forward and backward, blocks smaller than
    the sequence so that the causal loop bounds matter."""
    monkeypatch.setattr(flash, "CAUSAL_BLOCK_Q", 32)
    monkeypatch.setattr(flash, "CAUSAL_BLOCK_K", 16)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 32), jnp.float32)
               for kk in jax.random.split(jax.random.key(12), 3))

    def loss(path):
        return lambda q, k, v: jnp.sum(jnp.sin(flash.causal_attention(
            q, k, v, 32 ** -0.5, path)))
    for a, b in zip(jax.grad(loss("fused"), (0, 1, 2))(q, k, v),
                    jax.grad(loss("lax"), (0, 1, 2))(q, k, v)):
        _close(a, b, 1e-4)
    _close(flash.causal_attention(q, k, v, 0.2, "fused"),
           flash.causal_attention(q, k, v, 0.2, "lax"), 1e-5)
    # the path is chosen by backend and shape alone
    assert flash.select_path(4096, 256) == "lax"          # this is a CPU
    monkeypatch.setattr(flash, "interpret_mode", lambda: False)
    assert flash.select_path(4096, 256) == "fused"
    assert flash.select_path(4096, 256, initializing=True) == "lax"
    assert flash.select_path(4096, 192) == "lax"
    assert flash.select_path(200, 256) == "lax"


def test_grouped_route_by_backend_and_rows(monkeypatch):
    assert moe.grouped_route(65536) == "ragged_dot"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.grouped_route(65536) == "megablox"
    assert moe.grouped_route(64) == "ragged_dot"
    assert moe.grouped_route(65536, initializing=True) == "ragged_dot"
    # the compact buffer is whole row tiles, so it takes the kernel too
    assert moe.buffer_capacity(65536, 8, 64) == 16384
    assert moe.grouped_route(moe.buffer_capacity(4 * 1000, 8, 64)) == "megablox"
    assert moe.buffer_capacity(4 * 1000, 8, 64) == 1024
    assert moe.buffer_capacity(4 * 100, 8, 64) == 400      # never past the full one
    assert moe.buffer_capacity(65536, 64, 64) == 65536


@pytest.fixture(scope="module")
def micro_run(tmp_path_factory):
    return _step_scopes.two_steps("glm_moe_lite_micro",
                                  tmp_path_factory.mktemp("glm_micro"))


def test_two_steps_through_build_trainer_on_a_token_npz(micro_run):
    import train as train_cli
    assert train_cli.model_task("glm_moe_lite_micro") == "language"
    assert train_cli.model_task("vit_micro_patch4_56") == "classification"
    trainer, seen = micro_run.trainer, micro_run.seen
    assert len(seen) == 2 and int(trainer.state.step) == 2
    assert all(np.isfinite(m["loss"]) and 5.5 < m["loss_main"] < 7 for m in seen)
    # the expert layers' counters arrive as the step's metrics, by layer
    for layer in ("layers_1", "layers_2", "mtp/block"):
        assert seen[0][f"moe/rows_held/{layer}"] \
            + seen[0][f"moe/rows_absent/{layer}"] == 8 * 32 * 4
        assert seen[0][f"moe/load_max_over_mean/{layer}"] >= 1.0
        # 4 of 16 experts held: half the choices' rows are the buffer, and a
        # batch that sends more goes through it twice
        assert seen[0][f"moe/buffer_rows/{layer}"] in (8 * 32 * 2, 8 * 32 * 4)
    kernels = {(e["name"], e["path"]) for e in micro_run.kernels
               if "name" in e}
    assert {("mla_attention", "lax"), ("expert_matmul", "ragged_dot")} <= kernels
    # the CPU's lax path names nothing: every block is computed again whole
    cores = [e for e in micro_run.kernels if e.get("name") == "mla_attention"]
    assert cores and not any(e["forward_kept"] for e in cores)
    feeds = micro_run.feeds
    assert feeds and feeds[0]["route"] == "array_gather"
    assert feeds[0]["wire_dtype"] == {"tokens": "int32"}
    assert "loss_sum" in trainer.evaluate()


def test_every_scope_of_the_vocabulary_names_ops_of_the_step(micro_run):
    _step_scopes.check_decoder_vocabulary(micro_run.paths)


def test_glue_stays_outside_the_cores_and_little_is_unplaced(micro_run):
    _step_scopes.check_glue_and_unplaced(micro_run.paths, ("mla_core",))


@pytest.mark.parametrize("path", ["lax", "fused"])
def test_a_block_under_the_policy_is_the_block(setup, monkeypatch, path):
    """``decoder.remat_block``'s block against the un-remat'd one on the same
    weights: the same loss and leaf gradients; on the fused path (kernels
    interpreted) its gradient holds the un-remat'd block's three kernels and
    not a fourth, and its flight tally reads ``forward_kept``."""
    if path == "fused":
        monkeypatch.setattr(
            flash, "select_path", lambda tokens, width, initializing=False:
            "lax" if initializing else "fused")
    _, params, _ = setup
    cfg = MODELS.build("glm_moe_lite_micro").cfg
    x = jax.random.normal(jax.random.key(21), (2, 32, 64), jnp.float32)
    recorder = flight.get_recorder()
    results = {}
    for name, cls in (("kept", glm._RematBlock), ("whole", glm.DecoderBlock)):
        recorder.clear()
        block = cls(cfg, False, jnp.float32)
        grad = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            block.apply({"params": p}, x))), (0, 1))
        calls = sum(e.primitive.name == "pallas_call" for e in
                    audit.iter_eqns(jax.make_jaxpr(grad)(params["layers_1"], x)))
        assert calls == (3 if path == "fused" else 0), (name, calls)
        results[name] = grad(params["layers_1"], x)
        tally, = [e for e in recorder.events("kernel")
                  if e.get("name") == "mla_attention"]
        assert tally["path"] == path
        assert tally["forward_kept"] is (name == "kept" and path == "fused")
    assert float(results["kept"][0]) == float(results["whole"][0])
    for kept, whole in zip(*(jax.tree.leaves(results[n][1])
                             for n in ("kept", "whole"))):
        _close(kept, whole, 1e-6)


def test_flops_functions_against_a_count_of_the_references_products(
        monkeypatch):
    """Every matrix product of the reference goes through ``ops.einsum``:
    count their multiply-accumulates. With every published expert held and
    chosen (so that the plain loop over experts does the required rows) and
    the queries a row at a time (so that the causal blocks hold the required
    pairs and no more), the count is the FLOPs function's."""
    shapes = {**SHAPES, "n_routed_experts": 4, "n_routed_experts_published": 4,
              "seq_len": 8}
    macs = []
    real = ref_ops.einsum

    def counting(spec, a, b, mode):
        ins, _ = spec.replace("...", "").split("->")
        sizes, lead = {}, 1
        for names, x in zip(ins.split(","), (a, b)):
            sizes.update(zip(names[::-1], x.shape[::-1]))
            lead *= int(np.prod(x.shape[: x.ndim - len(names)]))
        macs.append(int(np.prod(list(sizes.values()))) * lead)
        return real(spec, a, b, mode)
    def unrolled(body, carry, xs):
        # the reference rolls its loop over the held experts: count each turn
        for i in range(len(jax.tree.leaves(xs)[0])):
            carry, _ = body(carry, jax.tree.map(lambda a: a[i], xs))
        return carry, None
    monkeypatch.setattr(ref_ops, "einsum", counting)
    monkeypatch.setattr(jax.lax, "scan", unrolled)
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)  # trace each
    monkeypatch.setattr(ref, "ATTENTION_ROWS", 1)
    params = ref.make_params(ref.param_spec(shapes), 0)
    rows = jnp.zeros((1, 8), jnp.int32)
    ref.forward(params, rows, rows, shapes, "f32", remat=False)
    assert sum(macs) == flops.forward_macs(shapes)
    assert flops.train_flops(shapes) == 6 * flops.forward_macs(shapes)


def test_flops_of_the_cell_are_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "glm47_flash_ep8.json")))
    s = cfg["shapes"]
    per_token = 2 * flops.forward_macs(s) / s["seq_len"]
    assert abs(per_token / 1e6 - 956.9) < 0.5
    assert abs(flops.train_flops(s) / 1e12 - 11.76) < 0.01
    assert flops.expected_rows_per_token(s) == 0.5
    work = flops.attention_work(s, 4)
    # causal scores: 42 MFLOP a token and layer forward, six blocks
    assert abs(work["flops"] / (3 * 6 * 4 * 4096) / 1e6 - 42.0) < 0.1
    # the file holds every number of the published config under its key
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"GLM-4.7-Flash"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    for key, value in s.items():
        if key in cfg and not isinstance(value, bool):
            assert cfg[key] == value, key


def _readings_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        ROOT, "benchmarks", "tools", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _token_controls(batches):
    """``readings_tokens.py``'s: the fp8 control, half of the batch's rows
    and the family's own fault."""
    return _readings_tool("readings_tokens").planted(len(batches[0]))


def _row_controls(batches):
    """``readings_row.py``'s: the second half of each row's positions left
    out, and the family's own fault."""
    readings_row = _readings_tool("readings_row")
    half = readings_row.half_positions(batches)
    n = batches[0].shape[1] - 1
    assert [b.shape for b in half] == [(len(b), n // 2 + 1) for b in batches]
    assert all(np.array_equal(h, b[:, :n // 2 + 1])
               for h, b in zip(half, batches))
    return readings_row.planted(batches)


# each language family: (bench file, workload, what the readings tool plants
# in the program's place, expert layers a step or None). The family's own
# fault is the keyword the harness hands every family: for GLM the correction
# bias left out of the choice, for Mellum2 the window left off the sliding
# layers, for Granite the state not carried from one chunk to the next.
REHEARSALS = {
    "glm": ("bench_rehearse_tokens.json", "rehearse_glm_micro",
            _token_controls, 3),
    "mellum": ("bench_rehearse_mellum.json", "rehearse_mellum_micro",
               _token_controls, 4),
    "granite": ("bench_rehearse_granite.json", "rehearse_granite_micro",
                _row_controls, None),
}


@pytest.mark.parametrize("family", sorted(REHEARSALS))
def test_token_driver_rehearsal_and_its_controls(family):
    """A whole run of the benchmark's token driver on the CPU at the
    family's rehearsal size comes out correct; what the readings tool plants
    in the program's place (the reference in fp8, half of the batch, or of
    each row's positions for the one-row granite batch, and the family's own
    fault) and a state left unchanged do not."""
    from benchmarks import run as bench_run
    from benchmarks.drivers.train_tokens import compare
    from benchmarks.harness import check
    from benchmarks.references import train_ref
    bench_file, workload, controls, expert_layers = REHEARSALS[family]
    line, run = bench_run.execute(
        workload, 424243, 0.3, False, require_tpu=False,
        bench_file=os.path.join(ROOT, "benchmarks", "tests", bench_file))
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["rows_wrong"]["value"] == 0.0
    if expert_layers is not None:
        assert run.facts["expert_layer_steps"] == \
            expert_layers * run.facts["steps"]
    assert run.facts["tokens_per_s"] > 0
    batches = run.reference_inputs
    fam = train_ref.family(run.config["family"])
    # one draw of the starting weights for every control; ``follow``
    # consumes what it is handed, so each takes a copy
    params = fam.make_params(fam.param_spec(run.config["shapes"]), 424243)
    common = dict(fam_name=run.config["family"], shapes=run.config["shapes"],
                  recipe=run.config["recipe"], batches=batches,
                  rows=run.traffic["reference_rows"])
    for name, kw in controls(batches).items():
        out = train_ref_lm.follow(params=jax.tree.map(jnp.copy, params),
                                  **{**common, **kw})
        ok, rows = check.judge({**compare(out, run.reference),
                                "rows_wrong": 0.0}, run.checks["limits"])
        assert not ok, (name, rows)
        del out
    still = dict(run.reference,
                 change=[0.0 * x for x in run.reference["change"]])
    ok, _ = check.judge({**compare(still, run.reference), "rows_wrong": 0.0},
                        run.checks["limits"])
    assert not ok

