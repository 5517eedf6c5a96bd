"""Online post-training (RLHF-style) over the live serving engine.

ROADMAP item 4: the workload only the trainer + server combination
enables — generate rollouts on ``serving.Engine`` (continuous batching,
per-token logprob capture), score them with a pluggable reward, update
the policy through the existing ``fit``/grad-accum/FSDP path, and push
the new weights into the live engine with ``Engine.update_weights`` —
no restart, in-flight KV retained under a documented staleness contract
(docs/RL.md).

    engine = dtpu.serving.Engine(model, max_slots=8, block_size=16,
                                 temperature=1.0)
    pt = dtpu.rl.PostTrainer(model, engine,
                             reward_fn=dtpu.rl.length_penalized_logprob())
    rows = pt.train(prompts, iterations=4, num_samples=4)

tests/test_rl.py runs the loop closed: rollouts, an update, the
weight sync with its version accounting, and the reward
improving across iterations (docs/RL.md).
"""

from .distill import DraftDistiller, distill_loss, pack_distill
from .loop import PostTrainer, Rollout, pack_rollouts, rl_loss
from .rewards import ToyPreferenceModel, length_penalized_logprob
from . import rewards

__all__ = [
    "PostTrainer",
    "Rollout",
    "pack_rollouts",
    "rl_loss",
    "DraftDistiller",
    "distill_loss",
    "pack_distill",
    "rewards",
    "ToyPreferenceModel",
    "length_penalized_logprob",
]
