"""The Nemotron 3 Nano configuration at a small size on the CPU: its
weights come in the program's layout, and the program's stack of
one-mixer layers (EMEMEM*) gives the loss and gradients of the plain
reference in `bench/configs/nemotron3-nano-30b-a3b.py`; the cell's
correctness check passes a sound run and fails the int8 control and each
planted fault."""
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_hybrid
from bench import check, harness
from repro.models import transformer
from repro.models.transformer import LM

MODEL = tiny_hybrid.MODEL


def exact_ce(h, embedding, labels, chunk=512):
    """The program's loss with its head in float32: `chunked_ce_loss`
    rounds the head's inputs to bfloat16 by design (the config's
    `compute`), which the float32 reference does not."""
    logits = jnp.einsum("bsd,vd->bsv", h, embedding,
                        precision=jax.lax.Precision.HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@pytest.fixture(scope="module")
def tiny():
    r = tiny_hybrid.cell()
    cfg = r["cfg"]
    return cfg, MODEL.init(cfg, jax.random.PRNGKey(3))


def test_weights_come_in_the_program_layout(tiny):
    cfg, params = tiny
    lm = LM(MODEL.arch(cfg), dtype=jnp.float32)
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                        params, want) == jax.tree.map(lambda _: True, want)


def test_program_matches_the_reference(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setattr(transformer, "chunked_ce_loss", exact_ce)
    lm = LM(MODEL.arch(cfg), dtype=jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                             cfg["vocab_size"])
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lm.loss)(params, batch)
    ref_loss, ref_grads = jax.value_and_grad(MODEL.reference_loss, argnums=1)(
        cfg, params, batch, "float32")
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for path, g, r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                          jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        scale = float(jnp.linalg.norm(r))
        if scale == 0.0:              # the selection-only bias
            assert float(jnp.linalg.norm(g)) == 0.0, path
            continue
        assert float(jnp.linalg.norm(g - r)) <= 1e-4 * scale, path


def test_train_flops_count_the_held_share_of_the_slots(tiny):
    cfg, _ = tiny
    wider = dict(cfg, held_experts=[0, 2 * cfg["held_experts"][1]])
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_expert = 3 * 4 * d * f * cfg["num_experts_per_tok"] \
        / cfg["router_experts"]
    extra = cfg["hybrid_override_pattern"].count("E") * per_expert \
        * cfg["held_experts"][1]
    assert MODEL.train_flops(wider, 64) - MODEL.train_flops(cfg, 64) \
        == pytest.approx(extra)


def test_config_keeps_every_published_width():
    cfg = json.loads((tiny_hybrid.harness.BENCH / "configs"
                      / "nemotron3-nano-30b-a3b.json").read_text())
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["norm_eps"]) == \
        (2688, 64, 64, 8, 128, 128, 1856, 3712, 128, 6, 32, 2, 128, 1e-5)
    assert cfg["hybrid_override_pattern"] in cfg["published"][
        "hybrid_override_pattern"]
    assert cfg["n_routed_experts"] == cfg["held_experts"][1] \
        - cfg["held_experts"][0]


# ------------------------------------------------- the cell's own check
SEED = 2 ** 31 + 5


@contextlib.contextmanager
def half_sequence():
    """The cell's batch is one sequence, so the path's half-batch fault
    would leave nothing: every local step here sees the first half of each
    sequence and takes the mean over it."""
    loss = LM.loss

    def halved(self, params, batch):
        return loss(self, params, jax.tree.map(
            lambda x: x[:, : x.shape[1] // 2], batch))
    LM.loss = halved
    try:
        yield
    finally:
        LM.loss = loss


def _run(planted=None):
    with planted() if planted else contextlib.nullcontext():
        return harness.run_cell(tiny_hybrid.cell(), SEED, 0.2, False,
                                t_start=time.perf_counter(), on_chip=False)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["check"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_sequence"])
def test_planted_fault_is_not_correct(fault):
    planted = half_sequence if fault == "half_sequence" else harness.load_module(
        harness.BENCH / "paths" / "pod.py").FAULTS[fault]
    out = _run(planted)
    assert not out["correct"], out["check"]


def test_control_is_not_correct():
    r = tiny_hybrid.cell()
    got = harness.readings(r, SEED, control=True, on_chip=False)
    assert check.verdict(got["program"], r["limits"])[0], got["program"]
    assert not check.verdict(got["control"], r["limits"])[0], got["control"]
