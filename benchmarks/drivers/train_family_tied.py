"""``drivers/train_family.py`` for a family whose head is its embedding's
own table (LFM2-MoE). That driver holds a fit's first loss within 0.2 of
ln(vocabulary rows), which is what a head that starts near uniform reads. A
tied head does not start there: its rows are the embedding's, normal(0.02),
and against a normed row of length sqrt(d) a fresh stack's logits are about
sqrt(d) x 0.02 wide (0.9 at d = 2048), so the cross-entropy of labels the
logits know nothing of stands half their mean square above ln(rows) (the
mean of log-sum-exp over rows of variance s^2 is ln(rows) + s^2 / 2 to
second order). Here the same limit is put on the first loss less half the
mean square of the reference's first logits (``reference_logit_mean_square``
of the family's ``first_step_checks``; the loss as a whole is held to the
reference's by ``loss_agrees``). Everything else, the run, its timing and
every other check, is that driver's, which a ``model_config`` PR may not
edit: it runs with its own limit on the whole first loss lifted, so that its
verdict is that of all its other checks, and the replaced check is and-ed to
it, as ``train_family_aux.py`` does for an auxiliary loss.

The family's stack is a pattern of unlike layers, so the run is also held to
having assembled the pattern its configuration states: the program's gauges
``model.layers_{conv,attention,dense,experts}`` against ``layer_types`` and
``num_dense_layers`` (``layers_built_as_configured``). A program without the
gauges cannot run the family at all."""

from __future__ import annotations

import math

from benchmarks.drivers import train_family
from benchmarks.drivers.train import FIRST_LOSS_TOL


def layers_configured(config: dict) -> dict:
    """The blocks a configuration states, by the gauges' kinds."""
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    convs = kinds.count("conv")
    return {"conv": convs, "attention": len(kinds) - convs,
            "dense": dense, "experts": len(kinds) - dense}


def run(env) -> dict:
    train_family.FIRST_LOSS_TOL = math.inf
    try:
        result = train_family.run(env)
    finally:
        train_family.FIRST_LOSS_TOL = FIRST_LOSS_TOL
    checks = result["checks"]
    expected = math.log(env.family.vocab_rows(env.config)) + 0.5 * checks[
        "reference_logit_mean_square"]
    checks["first_loss_expected"] = expected
    checks["first_loss_near_ln_vocab"] = abs(
        checks["first_loss"] - expected) < FIRST_LOSS_TOL
    from distributed_tpu.obs.registry import default_registry

    gauges = default_registry().snapshot()["gauges"]
    want = layers_configured(env.config)
    checks["layers_built"] = {
        kind: gauges.get(f"model.layers_{kind}") for kind in want}
    checks["layers_built_as_configured"] = checks["layers_built"] == want
    result["correct"] = bool(
        result["correct"] and checks["first_loss_near_ln_vocab"]
        and checks["layers_built_as_configured"])
    return result
