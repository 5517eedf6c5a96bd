"""Train the 136M LM at 64k context on ONE 16 GB TPU chip.

The recipe, each piece measured in docs/PERF_ROUNDS_1-5.md:

1. **Pallas flash attention** (automatic in MultiHeadAttention): O(T)
   attention memory instead of the (T, T) score matrix.
2. **remat** with ``dots_with_no_batch_dims_saveable``: per-block
   activation residuals are recomputed in backward, so depth stops
   multiplying T in memory.
3. **compile(head_chunks=8)**: the vocab head + loss run over token
   chunks in a rematerialized scan — the (T, vocab) logits (4.3 GB bf16
   at T=65k, V=32k, doubled by the backward cotangent) never exist.
   Without this the 64k step cannot even compile on the chip.

Measured single v5e chip, pre-PR-1 code (docs/PERF_ROUNDS_1-5.md): 8,756 tok/s at T=65,536
(MFU 0.352) — the ladder from 16k (0.380) to 64k is nearly flat.

Beyond one chip, shard the sequence itself with
``dtpu.DataSeqParallel`` (zigzag ring or Ulysses attention) — see
README "Long context" and tests/test_ring_attention.py.

Run: PYTHONPATH=. python examples/long_context.py [--seq 65536]
(first compile is minutes at 64k; CPU smoke: --seq 512 --layers 2)
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import distributed_tpu as dtpu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=65536)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--head-chunks", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    model = dtpu.Model(
        dtpu.models.transformer_lm(
            args.vocab,
            num_layers=args.layers,
            d_model=args.d_model,
            num_heads=args.heads,
            max_len=args.seq,
            dtype=jnp.bfloat16,
            remat=True,
            remat_policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    )
    model.compile(
        optimizer=dtpu.optim.Adam(1e-4),
        loss="pallas_sparse_categorical_crossentropy",
        metrics=[],
        head_chunks=args.head_chunks,
    )

    rng = np.random.default_rng(0)
    tok = rng.integers(0, args.vocab, (1, args.seq + 1), dtype=np.int64)
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)

    import time

    print(f"compiling + first step at T={args.seq} "
          f"(minutes at 64k; cached after)...")
    hist = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=1,
                     verbose=0)
    print(f"first loss: {hist.history['loss'][0]:.4f}")
    t0 = time.perf_counter()
    hist = model.fit(x, y, batch_size=1, epochs=1,
                     steps_per_epoch=args.steps, verbose=0)
    jax.block_until_ready(model.params)
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.seq / dt
    print(f"{args.steps} steps: {dt:.2f}s = {tok_s:,.0f} tokens/s "
          f"(loss {hist.history['loss'][0]:.4f})")


if __name__ == "__main__":
    main()
