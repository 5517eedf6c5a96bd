#!/usr/bin/env python3
"""What the first-step limits of the DeepSeek-V3 family's cell
(``benchmarks/families/deepseek_v3.py``) catch. The plain reference computes
a wrong model on purpose (no shared expert, gates left unnormalised, pairs
over a capacity dropped, the experts' matmuls or every weight matmul in
int8), at the cell's own size, weights and first batch for ``--seed``, and
stands in for the program in the driver's own comparison
(``reference.compare`` and ``family.first_step_checks``, as
``drivers/train_family.py`` calls them): its loss, its gradient as
``system_grads``, and the reference held to the wrong model's own choices
of experts. Each wrong model has to fail a check. ``int8`` is the cell's
control, the precision below the configuration's bfloat16: run it on the
chip beside the cell's own runs. Run by hand; PERF.md keeps the readings.

    chiprun --chips 1 -- python3 scripts/moe_wrong_models.py --variants int8
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
VARIANTS = ("no_shared", "unnormalised_gates", "capacity", "int8_experts",
            "int8")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--variants", default=",".join(VARIANTS))
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

    run = jax.jit(lambda variant, p: ref.loss_and_grads(
        p, x, y, kw=kw, variant=variant), static_argnums=(0,))
    pairs = int(tr["seq_len"]) * int(cfg["num_experts_per_tok"])
    out = {"seed": args.seed, "backend": jax.default_backend(),
           "variants": {}}
    for variant in args.variants.split(","):
        loss, grads, chosen = run(variant, p)
        compared = jax.device_get(ref.compare(
            p, x, y, kw=kw, system_grads=grads,
            forced=[c[0] for c in chosen]))
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
