#!/usr/bin/env python3
"""What the first-step limits of an expert-layer family's cell
(``benchmarks/families/deepseek_v3.py``, ``keye_vl2.py``, ``lfm2_moe.py``,
``laguna.py``)
catch. The plain reference computes a wrong model on purpose (DeepSeek-V3:
no shared expert, gates left unnormalised, pairs over a capacity dropped,
the experts' matmuls or every weight matmul in int8; Keye-VL-2.0: every
weight matmul in int8, the learned selection of keys ignored, half the keys
selected; LFM2-MoE: every weight matmul in int8, the short convolutions'
taps without their look-back, a head that hands the table no gradient;
Laguna: every weight matmul in int8, full causal attention in the sliding
layers, no gate, all 128 dimensions rotated unscaled in the full layers), at
the cell's own size, weights and first batch for ``--seed``, and stands in for
the program in the driver's own comparison (``reference.compare`` and
``family.first_step_checks``, as ``drivers/train_family.py`` calls them):
its loss, its gradient as ``system_grads``, and the reference held to the
wrong model's own choices of experts and keys. Each wrong model has to fail
a check. ``int8`` is a cell's control, the precision below the
configuration's bfloat16; ``no_selection`` is the Keye cell's second, and
``half_selection`` its third: a selection that keeps too few keys agrees
with the reference held to it in everything but the keys it missed;
``no_lookback`` and ``untied_head`` are the LFM2 cell's second and third;
``full_causal``, ``no_gate`` and ``plain_rope`` the Laguna cell's second to
fourth. Run them on the chip beside the cell's own runs. Run by hand; PERF.md keeps
the readings.

    chiprun --chips 1 -- python3 scripts/moe_wrong_models.py --variants int8
    chiprun --chips 1 -- python3 scripts/moe_wrong_models.py \
        --cell keye-vl2-30b.train.dsa8k
    chiprun --chips 1 -- python3 scripts/moe_wrong_models.py \
        --cell lfm2-8b-a1b.train.ep4share
    chiprun --chips 1 -- python3 scripts/moe_wrong_models.py \
        --cell laguna-xs2.train.swa8k
    JAX_PLATFORMS=cpu python3 scripts/moe_wrong_models.py [--seed N]

(float32 at "highest" on either backend; on the CPU some minutes a model
and 10 GB.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness, traffic as traffic_lib  # noqa: E402

CELL = "kanana2-30b.train.ep8share"
VARIANTS = {
    "deepseek_v3": ("no_shared", "unnormalised_gates", "capacity",
                    "int8_experts", "int8"),
    "keye_vl2": ("int8", "no_selection", "half_selection"),
    "lfm2_moe": ("int8", "no_lookback", "untied_head"),
    "laguna": ("int8", "full_causal", "no_gate", "plain_rope"),
}


def held_to(own):
    """A wrong model's own choices (what its ``loss_and_grads`` returns
    beside the gradient) in the form that function takes as ``forced``."""
    if isinstance(own, tuple):  # keye_vl2: (selections, experts, sum of L_I)
        keys, chosen, _ = own
        return {"experts": chosen, "keys": keys}
    if isinstance(own, dict):   # lfm2_moe, laguna: its experts, a sequence's
        return own["experts"]
    return [c[0] for c in own]


def forced_of(held):
    """``held_to``'s choices in the form the family's ``compare`` takes."""
    import jax.numpy as jnp

    if isinstance(held, dict):
        return dict(held, keys=[jnp.packbits(k, axis=-1)
                                for k in held["keys"]])
    return held


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--variants", default=None,
                    help="default: every wrong model of the cell's family")
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--manifest", default=harness.MANIFEST)
    args = ap.parse_args()

    import jax

    import distributed_tpu as dtpu

    if jax.default_backend() != "cpu":
        harness.enable_compile_cache()  # compare's program, from the cell
    manifest = harness.load_manifest(args.manifest)
    cell = harness.entry(manifest, "workloads", args.cell)
    cfg = harness.load_json(os.path.join(ROOT, harness.entry(
        manifest, "configs", cell["config"])["file"]))
    tr = harness.load_json(harness.find_file(
        manifest, "traffic", cell["traffic"]))
    fam = harness.load_module(manifest, "families", cfg["family"])
    ref = harness.load_module(manifest, "reference", cfg["family"])
    x, y = traffic_lib.train_batches(tr, int(cfg["vocab_size"]), args.seed)
    x, y = x[:1], y[:1]
    model = dtpu.Model(fam.build_module(cfg))
    model.compile(optimizer="sgd", loss=tr["loss"], metrics=())
    model.build((int(tr["seq_len"]),), seed=args.seed)
    kw = fam.reference_kwargs(cfg)
    p = fam.reference_params(model.params, model.state, cfg)

    run = jax.jit(lambda variant, p, forced: ref.loss_and_grads(
        p, x, y, kw=kw, variant=variant, forced=forced), static_argnums=(0,))
    pairs = int(tr["seq_len"]) * int(cfg["num_experts_per_tok"])
    out = {"seed": args.seed, "backend": jax.default_backend(),
           "variants": {}}
    variants = (args.variants.split(",") if args.variants
                else VARIANTS[cfg["family"]])
    for variant in variants:
        # Its choices first, then its loss and gradient held to them, as
        # ``compare`` holds the reference: on the v5e the two programs of
        # one reference, choosing for itself and held to those choices, read
        # 0.016-0.044 apart in the worst leaves (PERF.md section 6, PR 32).
        held = held_to(run(variant, p, None)[2])
        loss, grads, _ = run(variant, p, held)
        compared = jax.device_get(ref.compare(
            p, x, y, kw=kw, system_grads=grads, forced=forced_of(held)))
        del held
        row = fam.first_step_checks(
            float(loss), float(ref._norm(grads)), compared, pairs)
        del grads
        row["caught_by"] = [k for k in fam.FIRST_STEP_CHECKS if not row[k]]
        out["variants"][variant] = row
        print(json.dumps({variant: row}), file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0 if all(r["caught_by"] for r in out["variants"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
