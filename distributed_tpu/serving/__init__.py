"""Inference serving runtime: continuous batching over a paged KV cache.

The training side of the framework ends at a trained, checkpointed model;
this package is the serving side (ROADMAP open item 1): a request
scheduler with iteration-level continuous batching, a paged/block KV
cache so heterogeneous sequence lengths share one HBM pool, and a
prefill/decode split so long prompts never crawl through the one-token
decode loop.

    engine = dtpu.serving.Engine(model, max_slots=8, block_size=16)
    outs = engine.run([dtpu.serving.Request(prompt, max_new_tokens=64),
                       ...])
    engine.last_run_telemetry  # tokens/s, TTFT, kv_utilization, stalls

Greedy decode (``temperature=0``) is token-identical per request to
``model.generate()``; sampled decode is bit-reproducible per request
(``Request.seed``) and can capture per-token logprobs
(``run(return_logprobs=True)``); ``Engine.update_weights`` hot-swaps
served weights without a restart (the ``rl.PostTrainer`` sync seam —
docs/RL.md). What continuous batching saves over a static-batch
server, and when: docs/SERVING.md.

Memory-economy levers (docs/SERVING.md "Prefix caching & speculative
decoding"): ``Engine(prefix_cache=True)`` shares common prompt prefixes
across requests through a refcounted, copy-on-write block store;
``kv_dtype="int8"`` quantizes the KV pools behind the ``decode_dtype``
seam (more concurrent slots, fidelity-gated); ``draft_model=`` enables
speculative decoding — k candidate tokens verified in one fixed-shape
dispatch, token-exact against vanilla decode under greedy and pinned
seeds. tests/test_prefix.py pins all three.
"""

from .engine import Engine
from .kv_cache import BlockAllocator, PagedKVCache, PrefixStore
from .scheduler import Request, Scheduler, Sequence

__all__ = [
    "Engine",
    "Request",
    "Scheduler",
    "Sequence",
    "BlockAllocator",
    "PagedKVCache",
    "PrefixStore",
]
