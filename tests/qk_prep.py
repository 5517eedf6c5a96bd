"""What a ``GroupedQueryAttention``'s gradient does to q and k between their
projections and the attention, and to the heads' outputs under its gate: a
helper of the layer guards in ``test_laguna.py``, ``test_keye_vl2.py``,
``test_lfm2_moe.py`` and ``test_head_gate.py``."""

import hashlib
import re

import jax
import jax.numpy as jnp

from distributed_tpu.obs.registry import default_registry

COUNTERS = ("attn.qk_prep_fused", "attn.qk_prep_xla")


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def gradient_jaxpr(layer, t, d, batch=1, counters=COUNTERS):
    """The jaxpr of the gradient of a train-mode call's summed output in the
    layer's leaves and its (batch, t, d) float32 input, traced on shapes
    alone, and what the trace added to ``counters`` (the two
    ``attn.qk_prep_*``)."""
    params, state, _ = jax.eval_shape(
        lambda k: layer.init(k, (t, d)), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, t, d), jnp.float32)
    loss = lambda p, x, state: jnp.sum(
        layer.apply(p, state, x, train=True)[0].astype(jnp.float32))
    read = lambda: [default_registry().counter_value(c) for c in counters]
    before = read()
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x, state)
    return jaxpr, tuple(int(b - a) for a, b in zip(before, read()))


def kernel_calls(jaxpr):
    """Names of the Pallas kernels the jaxpr calls, sorted, one a call."""
    return sorted(e.params["name"] for e in _eqns(jaxpr.jaxpr)
                  if e.primitive.name == "pallas_call")


def kernel_grids(jaxpr):
    """Name and grid of every Pallas kernel call of the jaxpr, in order."""
    return [(e.params["name"], e.params["grid_mapping"].grid)
            for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]


def float32_head_views(jaxpr, head_dim=128, scopes=("q_norm", "k_norm")):
    """Shapes of the float32 (B, T, H, head_dim) arrays that an equation
    under one of ``scopes`` makes, forward or backward."""
    return [v.aval.shape for e in _eqns(jaxpr.jaxpr)
            if any(s in str(e.source_info.name_stack) for s in scopes)
            for v in e.outvars
            if getattr(v.aval, "ndim", 0) == 4
            and v.aval.shape[-1] == head_dim and v.aval.dtype == jnp.float32]


def digest(jaxpr):
    """SHA-256 of the jaxpr's text with its addresses taken out (as
    ``test_flash_attention.py:PARENT_JAXPRS``)."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()
