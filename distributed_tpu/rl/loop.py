"""Online post-training: the rollout -> score -> train -> hot-swap loop.

``PostTrainer`` closes the loop between the two halves this codebase
already has — the serving ``Engine`` (continuous batching makes N
parallel sampled rollouts per prompt cheap) and the ``fit`` training
path (grad-accum, FSDP, mixed precision all compose) — with
``Engine.update_weights`` as the seam between them: every iteration ends
by hot-swapping the freshly trained params into the live engine, no
restart, in-flight KV retained (docs/RL.md).

One iteration:

1. **rollout** — ``engine.run(requests, return_logprobs=True)``: each
   prompt is expanded into ``num_samples`` requests with distinct
   per-request seeds (bit-reproducible sampling; see
   ``serving.Request.seed``), and the engine captures each generated
   token's sampling logprob in its fixed-shape dispatches.
2. **score** — a pluggable ``reward_fn(prompt, completion, logprobs)``
   (``rl.rewards``) scores every completed rollout.
3. **train** — a REINFORCE / simple-PPO policy-gradient step through the
   EXISTING ``Model.fit`` path: rollouts are packed into a fixed-shape
   ``(x, y)`` batch (``pack_rollouts``) where ``y`` carries [target
   token, advantage, rollout logprob, completion mask, kl coef] per
   position, and a custom loss (``rl_loss``) recomputes the policy
   logprobs under the current params and applies
   ``-advantage * logprob`` plus a KL-to-reference penalty anchored on
   the ROLLOUT logprobs (the k3 estimator, always >= 0). Advantage =
   reward - EMA baseline (``optim.EmaBaseline``).
4. **sync** — ``engine.update_weights(model.params)``: re-place the new
   masters under the engine's strategy and bump ``weights_version``.
   The next iteration's rollouts are on-policy again.

The trainer and the engine share one process group (and usually one
``Model`` object — the engine serves its own SNAPSHOT of the params, so
optimizer steps never perturb in-flight decodes between syncs). This is
deliberately the single-controller shape production RL systems argue
about: its three couplings are rollout tokens/s, train steps/s and
weight-sync latency, which each iteration's row reports (on the chip:
not measured).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence as SequenceT

import jax
import jax.numpy as jnp
import numpy as np

from .. import optim
from ..serving.scheduler import Request
from . import rewards as rewards_lib

__all__ = ["PostTrainer", "Rollout", "pack_rollouts", "rl_loss"]

_M63 = (1 << 63) - 1

# y-channel layout of a packed rollout batch (pack_rollouts / rl_loss).
_CH_TARGET, _CH_ADV, _CH_REF_LP, _CH_MASK, _CH_KL = range(5)


@dataclass
class Rollout:
    """One scored rollout: the full token row the engine returned
    (prompt + completion), where the prompt ends, the captured sampling
    logprobs (index-aligned with the completion), and the scalar
    reward/advantage the scorer and baseline assigned."""

    tokens: np.ndarray
    prompt_len: int
    logprobs: np.ndarray
    reward: float = 0.0
    advantage: float = 0.0

    @property
    def completion(self) -> np.ndarray:
        return self.tokens[self.prompt_len:]


def pack_rollouts(rollouts: SequenceT, train_len: int,
                  kl_coef: float = 0.0):
    """Pack scored rollouts into one fixed-shape teacher-forcing batch
    for the ``fit`` path: ``x`` is ``(B, L-1)`` int32 input tokens
    (``tokens[:-1]``, right-padded with 0), ``y`` is ``(B, L-1, 5)``
    float32 with per-position channels [target token, advantage, rollout
    logprob, mask, kl coef]. The mask selects exactly the positions whose
    TARGET is a completion token (position t predicts token t+1, so the
    completion of a ``p``-token prompt occupies positions p-1 ..
    p-1+len(completion)); prompt and pad positions carry zero weight, so
    the policy gradient touches only what the policy actually chose.
    ``L`` must cover every rollout (use the engine's ``max_len``) — a
    silent truncation would drop tail tokens from the update."""
    L = int(train_len)
    if L < 2:
        raise ValueError(f"train_len must be >= 2, got {train_len}")
    b = len(rollouts)
    if b == 0:
        raise ValueError("pack_rollouts needs at least one rollout")
    x = np.zeros((b, L - 1), np.int32)
    y = np.zeros((b, L - 1, 5), np.float32)
    y[:, :, _CH_KL] = float(kl_coef)
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
        y[i, : n - 1, _CH_TARGET] = toks[1:]
        lo = max(int(r.prompt_len) - 1, 0)
        hi = n - 1  # last position predicts the final completion token
        lps = np.asarray(r.logprobs, np.float32).reshape(-1)
        if lps.size < hi - lo:
            raise ValueError(
                f"rollout {i}: {lps.size} logprobs for {hi - lo} "
                "completion tokens — run the engine with "
                "return_logprobs=True"
            )
        y[i, lo:hi, _CH_ADV] = float(r.advantage)
        y[i, lo:hi, _CH_REF_LP] = lps[: hi - lo]
        y[i, lo:hi, _CH_MASK] = 1.0
    return x, y


def rl_loss(ppo_clip: Optional[float] = None):
    """The policy-gradient loss over a ``pack_rollouts`` batch, shaped as
    a standard ``loss_fn(logits, y)`` so it drops straight into
    ``Model.compile`` and rides every existing step body (grad-accum
    scan, multi-step dispatch, FSDP constraints, mixed precision).

    Per masked position: ``-advantage * logprob`` (REINFORCE; with
    ``ppo_clip`` the PPO clipped-surrogate on the importance ratio
    ``exp(logprob - rollout_logprob)`` instead) plus ``kl_coef`` times
    the k3 KL estimator ``exp(d) - 1 - d`` (d = rollout_lp - lp, always
    >= 0) anchoring the update to the policy that generated the rollouts.
    The kl coef rides in the batch (y channel 4), so an adaptive
    controller (``optim.AdaptiveKLCoef``) never forces a recompile."""
    clip = None if ppo_clip is None else float(ppo_clip)

    def loss(logits, y):
        tok = y[..., _CH_TARGET].astype(jnp.int32)
        adv = y[..., _CH_ADV]
        ref_lp = y[..., _CH_REF_LP]
        w = y[..., _CH_MASK]
        kl_coef = y[..., _CH_KL]
        logp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp_all, tok[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(w), 1.0)
        # Mask INSIDE the exponentials: pad positions carry arbitrary
        # logprobs, and exp() of those would overflow before the mask
        # could zero them (inf * 0 = nan).
        d = (ref_lp - lp) * w
        if clip is None:
            pg = -(w * adv * lp)
        else:
            ratio = jnp.exp(-d)
            pg = -w * jnp.minimum(
                ratio * adv, jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv
            )
        kl = jnp.exp(d) - 1.0 - d
        return jnp.sum(pg + kl_coef * w * kl) / denom

    return loss


class PostTrainer:
    """RLHF-style online post-training over a live serving engine.

    ``model``: the BUILT trainer model (any strategy — FSDP, grad-accum
    and precision policies compose through the fit path). It is
    (re)compiled here with the policy-gradient loss; any previous
    compile's optimizer state is replaced, exactly like every recompile.
    ``engine``: a ``serving.Engine`` over the same architecture, built
    with ``temperature > 0`` (greedy rollouts carry no exploration —
    enforced loudly). Usually it wraps the SAME model object: the engine
    serves its own snapshot, so training between syncs never perturbs
    in-flight decodes.

    ``kl_coef`` is a float or an ``optim.AdaptiveKLCoef`` (updated each
    iteration with the measured post-update KL). ``reward_fn`` follows
    the ``rl.rewards`` contract. ``train_len`` fixes the packed batch
    width (default: the engine's ``max_len`` — one train-step compile for
    the loop's lifetime).
    """

    def __init__(self, model, engine, reward_fn="length_penalized_logprob",
                 *, optimizer="adam", learning_rate: float = 1e-3,
                 kl_coef=0.0, ppo_clip: Optional[float] = None,
                 baseline_decay: float = 0.9,
                 train_len: Optional[int] = None,
                 grad_accum: Optional[int] = None,
                 measure_kl: bool = True, seed: int = 0):
        if not model.built:
            raise RuntimeError("Build the trainer model first")
        if engine.temperature <= 0.0:
            raise ValueError(
                "PostTrainer needs a sampling engine (temperature > 0): "
                "greedy rollouts are deterministic per prompt, so the "
                "policy gradient has nothing to explore"
            )
        self.model = model
        self.engine = engine
        self.reward_fn = rewards_lib.get(reward_fn)
        self.kl = kl_coef  # float or optim.AdaptiveKLCoef
        self.baseline = optim.EmaBaseline(decay=baseline_decay)
        self.train_len = int(train_len or engine.max_len)
        self.grad_accum = grad_accum
        self.measure_kl = bool(measure_kl)
        self.seed = int(seed)
        self.iteration = 0
        self.history: List[dict] = []
        if isinstance(optimizer, str):
            model.compile(optimizer=optimizer, loss=rl_loss(ppo_clip),
                          metrics=(), learning_rate=float(learning_rate))
        else:
            model.compile(optimizer=optimizer, loss=rl_loss(ppo_clip),
                          metrics=())

    # ------------------------------------------------------------- helpers
    @property
    def kl_coef(self) -> float:
        return self.kl.coef if hasattr(self.kl, "coef") else float(self.kl)

    def _request_seed(self, prompt_idx: int, sample_idx: int) -> int:
        """Distinct, reproducible seed per (iteration, prompt, sample):
        fresh exploration every iteration, bit-identical loops across
        runs with the same PostTrainer seed."""
        h = self.seed
        for part in (self.iteration, prompt_idx, sample_idx):
            h = (h * 0x100000001B3 + part + 1) & _M63
        return h

    def _measured_kl(self, x, y) -> float:
        """Mean post-update KL-to-rollout over the completion tokens (k3
        estimator on the re-scored batch) — the number an
        ``AdaptiveKLCoef`` steers on, and the drift the staleness
        contract talks about, measured rather than guessed."""
        logits = self.model.predict(x, batch_size=x.shape[0])
        logp_all = jax.nn.log_softmax(
            jnp.asarray(logits, jnp.float32), axis=-1
        )
        tok = jnp.asarray(y[..., _CH_TARGET], jnp.int32)
        lp = jnp.take_along_axis(logp_all, tok[..., None], axis=-1)[..., 0]
        lp = np.asarray(jax.device_get(lp))
        w = y[..., _CH_MASK]
        d = (y[..., _CH_REF_LP] - lp) * w
        kl = np.exp(d) - 1.0 - d
        return float(np.sum(w * kl) / max(np.sum(w), 1.0))

    # ------------------------------------------------------------- iterate
    def iterate(self, prompts, *, num_samples: int = 4,
                max_new_tokens: int = 32, train_epochs: int = 1) -> dict:
        """One closed-loop iteration over ``prompts`` (a list of 1-D int
        token arrays): ``num_samples`` sampled rollouts per prompt on the
        engine, scored, one policy-gradient update per ``train_epochs``
        through ``fit`` (batch = all rollouts; ``grad_accum`` splits it
        into microbatches), then a weight hot-swap into the engine.
        Returns (and appends to ``self.history``) the iteration's metrics
        row — rewards, loss, measured KL, and the three loop couplings:
        rollout tokens/s, train steps/s, weight-sync latency."""
        if num_samples < 1:
            raise ValueError(f"num_samples must be >= 1, got {num_samples}")
        self.iteration += 1
        reqs = [
            Request(np.asarray(p, np.int32), int(max_new_tokens),
                    seed=self._request_seed(pi, si))
            for pi, p in enumerate(prompts)
            for si in range(num_samples)
        ]
        t0 = time.perf_counter()
        outs = self.engine.run(reqs, return_logprobs=True)
        rollout_s = time.perf_counter() - t0
        rows = {
            r["request_id"]: r
            for r in self.engine.last_run_telemetry["requests"]
        }
        rollouts = []
        for req, out in zip(reqs, outs):
            plen = int(req.prompt.size)
            lps = np.asarray(
                rows[req.request_id]["logprobs"], np.float64
            )
            roll = Rollout(np.asarray(out, np.int64), plen, lps)
            roll.reward = float(
                self.reward_fn(out[:plen], roll.completion, lps)
            )
            rollouts.append(roll)
        rewards = np.asarray([r.reward for r in rollouts], np.float64)
        # Advantage against the PRE-update baseline (the first iteration
        # centers on its own mean — EmaBaseline's cold start), then fold
        # this batch in for the next one.
        base = (
            self.baseline.value if self.baseline.value is not None
            else float(rewards.mean())
        )
        for roll in rollouts:
            roll.advantage = roll.reward - base
        self.baseline.update(float(rewards.mean()))
        x, y = pack_rollouts(rollouts, self.train_len, self.kl_coef)
        t0 = time.perf_counter()
        hist = self.model.fit(
            x, y, batch_size=len(rollouts), epochs=int(train_epochs),
            shuffle=False, verbose=0, grad_accum=self.grad_accum,
        )
        train_s = time.perf_counter() - t0
        train_steps = int(train_epochs)
        measured_kl = self._measured_kl(x, y) if self.measure_kl else None
        if measured_kl is not None and hasattr(self.kl, "update"):
            self.kl.update(measured_kl)
        t0 = time.perf_counter()
        version = self.engine.update_weights(self.model.params)
        sync_s = time.perf_counter() - t0
        row = {
            "iteration": self.iteration,
            "num_rollouts": len(rollouts),
            "reward_mean": float(rewards.mean()),
            "reward_std": float(rewards.std()),
            "baseline": float(base),
            "mean_completion_tokens": float(
                np.mean([r.completion.size for r in rollouts])
            ),
            "loss": float(hist.history["loss"][-1]),
            "kl": measured_kl,
            "kl_coef": self.kl_coef,
            "rollout_s": round(rollout_s, 4),
            "rollout_tokens_per_sec": self.engine.last_run_telemetry[
                "tokens_per_sec"
            ],
            "train_s": round(train_s, 4),
            "train_steps": train_steps,
            "train_steps_per_sec": round(train_steps / train_s, 3),
            "weight_sync_s": round(sync_s, 4),
            "weights_version": version,
        }
        self.history.append(row)
        # Registry view of the closed loop: the latest iteration row is a
        # stored report, with the loop couplings (rollout rate, train
        # rate, sync latency, reward) as gauges/counters so a scraper can
        # watch post-training health without touching .history.
        from ..obs import registry as obs_registry

        reg = obs_registry.default_registry()
        reg.counter("rl/iterations")
        reg.counter("rl/rollouts", len(rollouts))
        reg.gauge("rl/reward_mean", row["reward_mean"])
        reg.gauge("rl/kl", measured_kl if measured_kl is not None else 0.0)
        reg.gauge("rl/weight_sync_s", row["weight_sync_s"])
        reg.gauge("rl/rollout_tokens_per_sec",
                  row["rollout_tokens_per_sec"])
        reg.set_report("rl.iteration", row)
        return row

    def train(self, prompts, *, iterations: int = 4, num_samples: int = 4,
              max_new_tokens: int = 32, train_epochs: int = 1) -> List[dict]:
        """Run ``iterations`` closed-loop iterations; returns their
        metric rows (also accumulated on ``self.history``)."""
        return [
            self.iterate(
                prompts, num_samples=num_samples,
                max_new_tokens=max_new_tokens, train_epochs=train_epochs,
            )
            for _ in range(int(iterations))
        ]
