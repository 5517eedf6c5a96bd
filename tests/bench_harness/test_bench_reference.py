"""The plain reference (benchmarks/reference/gpt2.py) against
``models.transformer_lm`` at a tiny preset on the CPU: loss, gradients, and
the engine's log-probabilities through chunked prefill then paged decode."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration in float32, so that the tolerance below pins
    the equations and not bfloat16 rounding."""
    import distributed_tpu as dtpu

    manifest = harness.load_manifest(os.path.join(HERE, "rehearsal.json"))
    cfg = dict(harness.load_json(os.path.join(
        HERE, "configs", "gpt2-tiny.json")), compute_dtype="float32")
    fam = harness.load_module(manifest, "families", "gpt2")
    ref = harness.load_module(manifest, "reference", "gpt2")
    model = dtpu.Model(fam.build_module(cfg))
    model.build((cfg["n_positions"],), seed=5)
    return cfg, fam, ref, model


def test_loss_and_gradients_agree(tiny):
    """Both sides compute in float32; they differ only in operation order
    (fused projections, softmax scaling), so 1e-4 relative is wide, and a
    wrong epsilon, mask, bias or activation form is far outside it."""
    import jax
    import jax.numpy as jnp

    from distributed_tpu.ops import losses

    cfg, fam, ref, model = tiny
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["vocab_size"], (3, 65)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    kw = dict(n_head=cfg["n_head"], eps=fam.layer_norm_epsilon(cfg))

    def system_loss(params):
        logits, _ = model.module.apply(params, model.state, jnp.asarray(x),
                                       train=True, rng=None)
        return jnp.mean(losses._per_example_sparse_cce(
            logits.reshape(-1, logits.shape[-1]), jnp.asarray(y).reshape(-1)))

    sys_loss, sys_grads = jax.value_and_grad(system_loss)(model.params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(fam.reference_params(p, cfg), x, y, **kw)
    )(model.params)
    assert float(sys_loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(sys_grads)[0],
            jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale + 1e-7, path
    ref_loss2, gnorm = ref.loss_and_grad_norm(
        fam.reference_params(model.params, cfg), x, y, **kw)
    want = np.sqrt(sum(float(jnp.sum(g * g))
                       for g in jax.tree_util.tree_leaves(sys_grads)))
    assert float(gnorm) == pytest.approx(want, rel=1e-4)
    assert float(ref_loss2) == pytest.approx(float(sys_loss), rel=1e-5)


def test_engine_log_probabilities_agree(tiny):
    """Chunked prefill (two chunks) then paged decode against one full
    forward pass of the reference, in float32: 1e-4 absolute on a
    log-probability (operation order only)."""
    import jax.numpy as jnp

    from distributed_tpu import serving

    cfg, fam, ref, model = tiny
    engine = serving.Engine(model, **harness.load_json(os.path.join(
        HERE, "traffic", "serve-tiny.json"))["engine"])
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg["vocab_size"], (n,)).astype(np.int32), m)
            for n, m in ((90, 6), (17, 9), (64, 3))]
    outs = engine.run(reqs, return_logprobs=True)
    params = fam.reference_params(model.params, cfg)
    for (prompt, new), out, row in zip(
            reqs, outs, engine.last_run_telemetry["requests"]):
        out = np.asarray(out)
        assert out.shape == (prompt.size + new,)
        logp = np.asarray(ref.log_probs(
            params, jnp.asarray(out), n_head=cfg["n_head"],
            eps=fam.layer_norm_epsilon(cfg)))
        at = np.arange(prompt.size - 1, prompt.size + new - 1)
        want = logp[at, out[prompt.size:]]
        assert np.max(np.abs(np.asarray(row["logprobs"]) - want)) < 1e-4
        assert np.array_equal(out[prompt.size:], logp[at].argmax(axis=1))
