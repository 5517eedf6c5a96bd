"""FSDP comm/compute overlap in the scanned block stack.

The ``Strategy.overlap_spec`` x ``nn.ScannedBlocks(overlap=)`` seam: under
an FSDP-family strategy the per-layer scan prefetches layer i+1's
parameter all-gather while layer i computes (double-buffered carry; the
gather is a replicated sharding constraint, so it is layout-only and
differentiable). The contract tested here:

- numerics are IDENTICAL to the non-overlapped scan (the gather changes
  when bytes move, never what they are) at rtol 2e-5 on the loss
  trajectory;
- fit telemetry attributes the structural win: exposed-comm fraction
  1.0 (every gather serial with compute) -> 1/L (only the layer-0 warm
  gather left on the critical path);
- ``overlap='require'`` is loud under a strategy with no gather;
  ``'auto'`` silently degrades to the plain scan.

Wall-clock hiding is an accelerator claim (single-host sim shares one
execution stream): on the chip, not measured.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import distributed_tpu as dtpu


def _data(vocab=64, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int64)
    return tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)


def _fit_losses(strategy, overlap, steps=3, vocab=64, seq=16, layers=2):
    with strategy.scope():
        model = dtpu.Model(dtpu.models.transformer_lm(
            vocab, num_layers=layers, d_model=16, num_heads=2, max_len=seq,
            scan=True, scan_overlap=overlap))
        model.compile(optimizer=dtpu.optim.Adam(1e-3),
                      loss="sparse_categorical_crossentropy")
    model.build((seq,), seed=0)
    x, y = _data(vocab=vocab, seq=seq)
    hist = model.fit(x, y, batch_size=x.shape[0], epochs=steps,
                     steps_per_epoch=1, verbose=0, seed=0)
    return [float(l) for l in hist.history["loss"]], model


def test_overlap_spec_seam(devices):
    """Base strategies opt out (None); FSDP's gather pins every ndim>=1
    leaf to the replicated layout — an explicit all-gather the scheduler
    can hoist off the critical path. The constraint only materializes
    when the gathered value is CONSUMED (GSPMD cancels an unconsumed
    gather-then-reshard), which is the scan-body situation: the gathered
    layer params feed the block's compute."""
    assert dtpu.SingleDevice().overlap_spec() is None
    assert dtpu.DataParallel().overlap_spec() is None
    fsdp = dtpu.FullyShardedDataParallel()
    gather = fsdp.overlap_spec()
    assert callable(gather)
    with fsdp.scope():
        model = dtpu.Model(dtpu.models.mnist_cnn())
        model.compile(optimizer="sgd",
                      loss="sparse_categorical_crossentropy")
        model.build((28, 28, 1))
    k = model.params["dense"]["kernel"]
    assert k.sharding.spec == PartitionSpec("fsdp", None)
    consumed = jax.jit(lambda p: (gather(p) * 1.0).sum())
    hlo = consumed.lower(k).compile().as_text()
    assert "all-gather" in hlo
    got = float(consumed(k))
    assert got == pytest.approx(float(np.asarray(k).sum()), rel=1e-5)


@pytest.mark.parametrize("layers", [2, 4])
def test_overlap_matches_off_numerics(devices, layers):
    """The tentpole parity gate: gather prefetch must not change a single
    loss value beyond reordering noise, and of the L gathers only layer
    0's warm one stays exposed."""
    ref, _ = _fit_losses(dtpu.FullyShardedDataParallel(), "off",
                         layers=layers)
    got, model = _fit_losses(dtpu.FullyShardedDataParallel(), "auto",
                             layers=layers)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=0)
    telem = model.last_fit_telemetry["overlap"]
    assert telem["overlap"] is True
    assert telem["layers"] == layers
    assert telem["exposed_comm_fraction"] == pytest.approx(1 / layers)


def test_every_stacked_block_leaf_is_really_gathered(devices):
    """What the scan prefetches is one all-gather for each stacked block
    leaf, in the compiled program and not only in the sharding rules:
    with a replicated output as the consumer, GSPMD keeps them all."""
    layers = 4
    fsdp = dtpu.FullyShardedDataParallel()
    with fsdp.scope():
        model = dtpu.Model(dtpu.models.transformer_lm(
            64, num_layers=layers, d_model=16, num_heads=2, max_len=16,
            scan=True))
        model.compile(optimizer="sgd",
                      loss="sparse_categorical_crossentropy")
    model.build((16,), seed=0)
    stacked = [l for l in jax.tree_util.tree_leaves(model.params)
               if l.ndim >= 2 and l.shape[0] == layers]
    assert len(stacked) == 16  # the leaves of one transformer block
    assert all(any(ax is not None for ax in l.sharding.spec)
               for l in stacked)
    gather = fsdp.overlap_spec()
    rep = NamedSharding(fsdp.mesh, PartitionSpec())
    hlo = jax.jit(lambda ps: [gather(p) for p in ps],
                  out_shardings=[rep] * len(stacked)
                  ).lower(stacked).compile().as_text()
    assert len(re.findall(r" all-gather\(", hlo)) == 16


def test_off_telemetry_reports_full_exposure(devices):
    _, model = _fit_losses(dtpu.FullyShardedDataParallel(), "off")
    telem = model.last_fit_telemetry["overlap"]
    assert telem["overlap"] is False
    assert telem["exposed_comm_fraction"] == 1.0


def test_auto_degrades_silently_without_gather():
    """SingleDevice has no overlap_spec: 'auto' must run the plain scan,
    report no overlap, and keep numerics."""
    losses, model = _fit_losses(dtpu.SingleDevice(), "auto", steps=2)
    ref, _ = _fit_losses(dtpu.SingleDevice(), "off", steps=2)
    np.testing.assert_allclose(losses, ref, rtol=1e-6)
    telem = model.last_fit_telemetry["overlap"]
    assert telem["overlap"] is False


def test_require_is_loud_without_gather():
    with pytest.raises(ValueError, match="overlap_spec"):
        _fit_losses(dtpu.SingleDevice(), "require", steps=1)


def test_scanned_blocks_validates_overlap_mode():
    with pytest.raises(ValueError, match="overlap"):
        dtpu.nn.ScannedBlocks(
            lambda: dtpu.nn.Dense(4), 2, overlap="sometimes")


def test_overlap_trace_records_activation(devices):
    """The record the scanned apply hands its program at trace time, as the
    fit's report reads it: layers + active flag."""
    _, model = _fit_losses(dtpu.FullyShardedDataParallel(), "auto", steps=1)
    rec = model.last_fit_telemetry["overlap"]
    assert rec == {"overlap": True, "exposed_comm_fraction": 0.5,
                   "layers": 2}
