"""``drivers/train_family.py`` for a family whose objective carries an
auxiliary loss of its layers beside the language-model loss (Keye-VL-2.0:
the indexers' L_I, about 0.3 a layer at initialisation). That driver holds a
fit's first loss within 0.2 of ln(vocabulary rows), which is what a head that
starts near uniform reads of the cross-entropy alone; here the same limit is
put on the first loss less the auxiliary part, as the reference computes it
(``reference_index_loss`` of the family's ``first_step_checks``; the loss as
a whole is held to the reference's by ``loss_agrees``). Everything else, the
run, its timing and every other check, is that driver's, which a
``model_config`` PR may not edit: it runs with its own limit on the whole
first loss lifted, so that its verdict is that of all its other checks,
whatever they are by then, and the replaced check is and-ed to it."""

from __future__ import annotations

import math

from benchmarks.drivers import train_family
from benchmarks.drivers.train import FIRST_LOSS_TOL


def run(env) -> dict:
    train_family.FIRST_LOSS_TOL = math.inf
    try:
        result = train_family.run(env)
    finally:
        train_family.FIRST_LOSS_TOL = FIRST_LOSS_TOL
    checks = result["checks"]
    lm_loss = checks["first_loss"] - checks["reference_index_loss"]
    checks["first_lm_loss"] = lm_loss
    checks["first_loss_near_ln_vocab"] = abs(
        lm_loss - math.log(env.family.vocab_rows(env.config))) < FIRST_LOSS_TOL
    result["correct"] = bool(
        result["correct"] and checks["first_loss_near_ln_vocab"])
    return result
