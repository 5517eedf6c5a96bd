"""Draft-LM distillation: make speculative decoding pay.

A speculative engine only wins when the draft's greedy chain agrees with
the target (docs/SERVING.md "Draft models & gossip"): every rejected
column is a wasted draft dispatch plus a verify row that committed one
token anyway. A randomly-initialized or layer-truncated draft agrees
almost never (an accept rate near 0.02 on a serving workload), so
speculation LOSES until the draft is trained toward the target.

``DraftDistiller`` closes that gap with the machinery the repo already
has, in the ``PostTrainer`` shape:

1. **rollout** — ``engine.run(requests, return_logprobs=True)``: the
   TARGET generates continuations, and the fixed dispatches capture each
   chosen token's logprob (the teacher signal) for free.
2. **distill** — rollouts are packed into one fixed-shape
   teacher-forcing batch (``pack_distill``) and the draft is trained
   through the existing ``Model.fit`` path with ``distill_loss``: the
   single-sample forward-KL estimate
   ``E_teacher[log p_teacher(tok) - log p_draft(tok)]`` over the
   completion positions. The teacher term is a constant w.r.t. the
   draft, so the gradient is exactly cross-entropy on the teacher's
   chosen tokens — but the LOSS value is the KL gap, which makes
   "distillation converged" mean "draft agrees with teacher".
3. **sync** — ``engine.update_weights(draft_params=...)``: the engine's
   draft snapshot is re-placed and a ``draft_sync`` event records how
   stale the draft had grown (target swaps since the last sync).

Greedy acceptance is the whole objective here, so distilling ON the
serving workload's prompts is not cheating — it is the point: the draft
memorizes the target's continuations for the traffic it will actually
front-run.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence as SequenceT

import jax
import jax.numpy as jnp
import numpy as np

from ..serving.scheduler import Request
from .loop import Rollout

__all__ = ["DraftDistiller", "pack_distill", "distill_loss"]

_M63 = (1 << 63) - 1

# y-channel layout of a packed distillation batch (pack_distill /
# distill_loss): [teacher-chosen token, teacher logprob, mask].
_CH_TOK, _CH_TLP, _CH_MASK = range(3)


def pack_distill(rollouts: SequenceT, train_len: int):
    """Pack teacher rollouts into one fixed-shape teacher-forcing batch:
    ``x`` is ``(B, L-1)`` int32 input tokens (``tokens[:-1]``,
    right-padded), ``y`` is ``(B, L-1, 3)`` float32 with per-position
    channels [teacher token, teacher logprob, mask]. The mask selects
    exactly the positions whose TARGET is a generated token — prompt
    predictions never affect acceptance (the draft is prefilled on real
    tokens), so they carry zero weight. Mirrors ``pack_rollouts``'s
    geometry; ``L`` must cover every rollout (the engine's max_len)."""
    L = int(train_len)
    if L < 2:
        raise ValueError(f"train_len must be >= 2, got {train_len}")
    b = len(rollouts)
    if b == 0:
        raise ValueError("pack_distill needs at least one rollout")
    x = np.zeros((b, L - 1), np.int32)
    y = np.zeros((b, L - 1, 3), np.float32)
    for i, r in enumerate(rollouts):
        toks = np.asarray(r.tokens, np.int64).reshape(-1)
        if toks.size > L:
            raise ValueError(
                f"rollout {i} has {toks.size} tokens but train_len is "
                f"{L}; raise train_len (the engine's max_len always "
                "covers its own outputs)"
            )
        n = toks.size
        x[i, : n - 1] = toks[:-1]
        y[i, : n - 1, _CH_TOK] = toks[1:]
        lo = max(int(r.prompt_len) - 1, 0)
        hi = n - 1
        lps = np.asarray(r.logprobs, np.float32).reshape(-1)
        if lps.size < hi - lo:
            raise ValueError(
                f"rollout {i}: {lps.size} logprobs for {hi - lo} "
                "completion tokens — run the engine with "
                "return_logprobs=True"
            )
        y[i, lo:hi, _CH_TLP] = lps[: hi - lo]
        y[i, lo:hi, _CH_MASK] = 1.0
    return x, y


def distill_loss():
    """Forward-KL distillation loss over a ``pack_distill`` batch,
    shaped as ``loss_fn(logits, y)`` for ``Model.compile`` (grad-accum,
    FSDP, precision policies all compose, exactly like ``rl_loss``).

    Per masked position: ``teacher_lp - log p_draft(teacher token)`` —
    the single-sample Monte-Carlo estimate of
    ``KL(teacher || draft)`` under the teacher's sampled trajectory.
    Non-negative in expectation, approaching 0 as the draft matches the
    teacher on-support; its gradient is plain cross-entropy (the teacher
    term is constant), so optimization is as stable as CE while the
    reported value stays interpretable as the agreement gap."""

    def loss(logits, y):
        tok = y[..., _CH_TOK].astype(jnp.int32)
        tlp = y[..., _CH_TLP]
        w = y[..., _CH_MASK]
        logp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp_all, tok[..., None], axis=-1)[..., 0]
        return jnp.sum(w * (tlp - lp)) / jnp.maximum(jnp.sum(w), 1.0)

    return loss


class DraftDistiller:
    """Distill a small draft LM toward a serving engine's target.

    ``engine``: a built ``serving.Engine`` over the TARGET model (greedy
    or sampled — greedy is the natural choice: acceptance compares the
    draft's greedy chain against the target's stream, and a greedy
    teacher makes the learning problem deterministic).
    ``draft``: the BUILT draft model to train — usually the same object
    the engine was constructed with ``draft_model=``; the engine serves
    its own SNAPSHOT of the draft params, so training here never
    perturbs in-flight speculation until :meth:`sync` publishes.

    ``train_len`` fixes the packed batch width (default: the engine's
    ``max_len`` — one train-step compile for the distiller's lifetime).
    """

    def __init__(self, engine, draft, *, optimizer="adam",
                 learning_rate: float = 1e-2,
                 train_len: Optional[int] = None, seed: int = 0):
        if not draft.built:
            raise RuntimeError("Build the draft model first")
        self.engine = engine
        self.draft = draft
        self.train_len = int(train_len or engine.max_len)
        self.seed = int(seed)
        self.rounds = 0
        self.history: List[dict] = []
        if isinstance(optimizer, str):
            draft.compile(optimizer=optimizer, loss=distill_loss(),
                          metrics=(), learning_rate=float(learning_rate))
        else:
            draft.compile(optimizer=optimizer, loss=distill_loss(),
                          metrics=())

    def _request_seed(self, prompt_idx: int, sample_idx: int) -> int:
        h = self.seed
        for part in (self.rounds, prompt_idx, sample_idx):
            h = (h * 0x100000001B3 + part + 1) & _M63
        return h

    # ------------------------------------------------------------ rollout
    def collect(self, prompts, *, max_new_tokens: int = 32,
                num_samples: int = 1) -> List[Rollout]:
        """Teacher rollouts for ``prompts`` (1-D int token arrays) on the
        engine, with per-token teacher logprobs captured in the fixed
        dispatches. ``num_samples > 1`` only diversifies a SAMPLING
        engine (distinct reproducible seeds per sample); a greedy engine
        would just repeat itself, so it is pinned to 1 there."""
        if self.engine.temperature <= 0.0:
            num_samples = 1
        reqs = [
            Request(np.asarray(p, np.int32), int(max_new_tokens),
                    seed=self._request_seed(pi, si))
            for pi, p in enumerate(prompts)
            for si in range(int(num_samples))
        ]
        outs = self.engine.run(reqs, return_logprobs=True)
        rows = {
            r["request_id"]: r
            for r in self.engine.last_run_telemetry["requests"]
        }
        return [
            Rollout(
                np.asarray(out, np.int64), int(req.prompt.size),
                np.asarray(rows[req.request_id]["logprobs"], np.float64),
            )
            for req, out in zip(reqs, outs)
        ]

    # ------------------------------------------------------------ distill
    def distill(self, rollouts: SequenceT, *, epochs: int = 8,
                batch_size: Optional[int] = None) -> dict:
        """Train the draft on ``rollouts`` through the fit path; returns
        (and appends to ``self.history``) the round's metrics row. The
        loss is the forward-KL gap — ``loss_first``/``loss_last`` make
        "did distillation move the draft toward the teacher" a direct
        telemetry read."""
        x, y = pack_distill(rollouts, self.train_len)
        self.rounds += 1
        t0 = time.perf_counter()
        hist = self.draft.fit(
            x, y, batch_size=int(batch_size or len(rollouts)),
            epochs=int(epochs), shuffle=False, verbose=0,
        )
        train_s = time.perf_counter() - t0
        losses = [float(v) for v in hist.history["loss"]]
        row = {
            "round": self.rounds,
            "num_rollouts": len(rollouts),
            "epochs": int(epochs),
            "loss_first": losses[0],
            "loss_last": losses[-1],
            "losses": losses,
            "train_s": round(train_s, 4),
        }
        self.history.append(row)
        from ..obs import registry as obs_registry

        reg = obs_registry.default_registry()
        reg.counter("rl/distill_rounds")
        reg.gauge("rl/distill_loss", losses[-1])
        reg.set_report("rl.distill", row)
        return row

    # --------------------------------------------------------------- sync
    def sync(self) -> int:
        """Publish the trained draft into the engine's served snapshot
        (``update_weights(draft_params=...)`` — emits ``draft_sync`` with
        the staleness the draft had accumulated). Returns the engine's
        weights_version."""
        return self.engine.update_weights(draft_params=self.draft.params)

    # ---------------------------------------------------------------- fit
    def fit(self, prompts, *, max_new_tokens: int = 32,
            num_samples: int = 1, epochs: int = 8,
            rounds: int = 1, sync: bool = True) -> List[dict]:
        """Convenience loop: ``rounds`` x (collect -> distill -> sync).
        The sync is per-round, not final-only, and it is load-bearing
        beyond freshness: ``fit`` DONATES the draft's param buffers
        (the in-place-update train step), so an engine still serving the
        pre-fit snapshot would read deleted buffers — exactly the
        PostTrainer ordering (rollout, train, hot-swap) applied to the
        draft arm. ``sync=False`` is for engines built WITHOUT a draft
        (distilling ahead of time); publish manually before speculating.
        Returns the per-round metric rows."""
        out = []
        for _ in range(int(rounds)):
            rollouts = self.collect(
                prompts, max_new_tokens=max_new_tokens,
                num_samples=num_samples,
            )
            out.append(self.distill(rollouts, epochs=epochs))
            if sync and getattr(self.engine, "_draft", None) is not None:
                self.sync()
        return out
