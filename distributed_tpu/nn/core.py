"""Functional layer system.

Design notes (TPU-first):

- A Layer is a *pure description*: construction stores hyperparameters only.
  Parameters live in plain nested-dict pytrees created by ``init`` and are
  threaded explicitly through ``apply``. This is the JAX idiom (init/apply)
  rather than the reference's object-holding-variables Keras idiom
  (/root/reference/README.md:292-298), and is what makes a whole train step
  jit-compilable and shardable with ``NamedSharding`` over a device mesh.
- ``apply`` is side-effect free: mutable layer state (e.g. BatchNorm running
  stats) is returned, never written in place, so XLA sees static dataflow.
- Shapes are static: ``init`` takes the (batch-free) input shape and performs
  shape inference once, in Python, outside any trace.

The public surface still *reads* like the reference's Keras Sequential UX
(/root/reference/README.md:58-68): ``Sequential([Conv2D(...), Flatten(),
Dense(...)])``.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

Params = Dict[str, Any]
State = Dict[str, Any]
Shape = Tuple[int, ...]


def _camel_to_snake(name: str) -> str:
    # Conv2D -> conv2d, MaxPool2D -> max_pool2d (split only at lower->Upper).
    return re.sub(r"(?<=[a-z])(?=[A-Z])", "_", name).lower()


class Layer:
    """Base class: hyperparameters in, pure init/apply out."""

    def __init__(self, name: Optional[str] = None):
        self.name = name  # finalized by the enclosing container (or init())
        self._name_explicit = name is not None

    # -- to be overridden ---------------------------------------------------
    def init(self, key: jax.Array, input_shape: Shape) -> Tuple[Params, State, Shape]:
        """Create (params, state, output_shape) for a given unbatched input shape."""
        raise NotImplementedError

    def apply(
        self,
        params: Params,
        state: State,
        x,
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
    ):
        """Run the layer on a batched input. Returns (output, new_state)."""
        raise NotImplementedError

    # -- incremental decode (KV-cache generation) ---------------------------
    # True means apply() treats every (batch of) position(s) independently,
    # so the default one-token decode below is exact. Layers that mix
    # positions (attention, positional embeddings, scanned block stacks)
    # either override decode() with a cached implementation or set this
    # False to fail loudly.
    decode_safe = True

    def init_cache(self, params: Params, batch: int, max_len: int, dtype):
        """Create this layer's decode cache (empty for stateless layers)."""
        return {}

    def decode(self, params: Params, state: State, cache, x, *, pos):
        """One autoregressive step: x is (B, 1, ...), pos the (traced)
        position index. Returns (output, new_cache)."""
        if not self.decode_safe:
            raise NotImplementedError(
                f"{type(self).__name__} does not support incremental "
                "decode (generation)"
            )
        out, _ = self.apply(params, state, x, train=False)
        return out, cache

    # -- paged decode (block KV cache, serving.Engine) ----------------------
    # The paged counterparts of init_cache/decode: instead of one dense
    # (B, max_len, ...) cache per sequence, attention layers write into a
    # shared pool of fixed-size blocks, addressed through per-slot block
    # tables — sequences of different lengths share one HBM pool
    # (vLLM-style PagedAttention). Slots also carry PER-SLOT positions
    # (a (S,) vector, not one scalar), which is what lets the serving
    # engine decode sequences at different depths in one fixed-shape
    # dispatch. Position-independent layers ride their existing decode()
    # (which ignores pos); position-dependent layers (attention,
    # positional embeddings) override.

    def init_paged_cache(self, params: Params, num_blocks: int,
                         block_size: int, dtype):
        """Create this layer's share of the paged KV pool (empty for
        layers that cache nothing)."""
        return {}

    def paged_decode(self, params: Params, state: State, cache, x, *,
                     block_tables, positions):
        """One decode step for a batch of SLOTS: x is (S, 1, ...),
        ``block_tables`` (S, max_blocks) int32 pool indices,
        ``positions`` (S,) int32 per-slot write/attend positions.
        Returns (output, new_cache)."""
        out, _ = self.decode(params, state, {}, x, pos=positions)
        return out, cache

    def paged_verify(self, params: Params, state: State, cache, x, *,
                     block_tables, positions):
        """Speculative verification for a batch of SLOTS: x is
        (S, K, ...) — K draft-proposed candidate tokens per slot at
        consecutive absolute positions [positions[s], positions[s]+K) —
        scored in one fixed-shape dispatch (K=1 is exactly paged_decode).
        Default: position-independent layers apply tokenwise (the K
        candidates are just more positions); position-dependent layers
        (attention, positional embeddings) override."""
        if not self.decode_safe:
            raise NotImplementedError(
                f"{type(self).__name__} does not support incremental "
                "decode (generation)"
            )
        out, _ = self.apply(params, state, x, train=False)
        return out, cache

    def paged_prefill(self, params: Params, state: State, cache, x, *,
                      block_table, start):
        """Prompt-chunk prefill for ONE sequence: x is (1, C, ...) covering
        absolute positions [start, start+C); writes this chunk's KV into
        the blocks named by ``block_table`` (max_blocks,) and returns
        (output, new_cache). Default: position-independent layers apply
        tokenwise and cache nothing."""
        if not self.decode_safe:
            raise NotImplementedError(
                f"{type(self).__name__} does not support incremental "
                "decode (generation)"
            )
        out, _ = self.apply(params, state, x, train=False)
        return out, cache

    # -- shared helpers -----------------------------------------------------
    def sharding_hints(self) -> Dict[str, str]:
        """Tensor-parallel roles for this layer's params: param name ->
        'col' (shard output dim over the model axis) or 'row' (shard input
        dim). Containers nest these to mirror the params tree; strategies
        translate roles into PartitionSpecs. Empty = fully replicated."""
        return {}

    def dtype_hints(self):
        """Explicit per-layer compute-dtype overrides, mirroring the params
        tree the way ``sharding_hints`` does: a layer constructed with
        ``dtype=...`` reports that dtype; containers nest children under
        their names. ``Policy.cast_to_compute`` skips the marked subtrees,
        so an explicitly-dtyped layer keeps master-precision params and
        performs its own cast — per-layer ``dtype=`` overrides the policy
        exactly. None/{} = no override (the policy's compute dtype
        applies)."""
        return getattr(self, "dtype", None)

    def default_name(self) -> str:
        return _camel_to_snake(type(self).__name__)

    def param_spec(self, input_shape: Shape) -> Dict[str, Shape]:
        """Shapes of this layer's parameters (used for sharding rules); optional."""
        return {}

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


class NameScope:
    """Assigns unique keras-style names ('conv2d', 'conv2d_1', ...) within a container."""

    def __init__(self):
        self._counts: Dict[str, int] = {}
        self._used = set()

    def assign(self, layer: Layer) -> str:
        if layer._name_explicit and layer.name:
            if layer.name in self._used:
                raise ValueError(f"Duplicate layer name {layer.name!r}")
            self._used.add(layer.name)
            return layer.name
        base = layer.default_name()
        n = self._counts.get(base, 0)
        self._counts[base] = n + 1
        name = base if n == 0 else f"{base}_{n}"
        self._used.add(name)
        return name


# Trace-time ambient values, each set for a block by ``_ambient``.
_AMBIENT = threading.local()


@contextlib.contextmanager
def _ambient(slot: str, value):
    prev = getattr(_AMBIENT, slot, None)
    setattr(_AMBIENT, slot, value)
    try:
        yield
    finally:
        setattr(_AMBIENT, slot, prev)


def eval_sample_weights(weights):
    """Trace-time ambient per-EXAMPLE validity weights (shape (B,)).

    The eval step pads its final batch to keep shapes static; layers whose
    statistics span the batch (MoE routing: load-balance aux loss,
    capacity competition) would otherwise count the pad rows. The eval
    steps wrap ``module.apply`` in this context and such layers read
    ``current_sample_weights()`` during tracing — the weights are a traced
    array, so they become a real input of the compiled step. Training
    never sets this (fit never pads), so the train graph is unchanged."""
    return _ambient("weights", weights)


def current_sample_weights():
    return getattr(_AMBIENT, "weights", None)


def child_scope(key: str):
    """The device scope a container opens around one child's ``apply``:
    ``with child_scope(key): child.apply(...)``, ``key`` being the child's
    key in the container's params tree. The ONE place a layer's scope comes
    from: every container applies its children under it, so the scope path
    an operation carries on the device
    (``residual_6/main/multi_head_attention``, read in XProf and by
    ``benchmarks/scopes.py``) IS its parameter path, which is already a
    checkpoint format. Names only: a scope adds to the operation's
    ``op_name`` metadata and changes no instruction. A transparent wrapper
    (``Remat``) opens none: it shares its inner layer's name and path.

    A context manager and not a function that calls ``apply``: a Python
    frame between a container and its child made tracing the 24-layer
    benchmark step 3.7 s (55%) slower on the chip's host, all of it inside
    the forward Pallas kernels' tracing (measured, PR 24; root PERF.md)."""
    return jax.named_scope(key)


# What layers put into ``Model.last_fit_telemetry``, each under a kind of
# its own: counters in their state (``counts``), or records handed to the
# program being traced (``trace_record``) and read by ``reports``.
_COUNTED: Dict[str, tuple] = {}  # kind -> (counter names, gauges)
_REPORTS: Dict[str, Any] = {}  # kind -> report(record, strategy)


def counts(kind: str, names, gauges=lambda layers: {}) -> tuple:
    """A layer keeps the float counters ``names`` (returned) in its state,
    reported under ``kind``; ``gauges``, given the kind's ``{layer path:
    {counter: value}}``, returns ``{gauge: value}`` to set when a fit ends."""
    _COUNTED[kind] = (tuple(names), gauges)
    return _COUNTED[kind][0]


def reports(kind: str):
    """Register ``report(record, strategy)``: ``kind``'s entry (None for
    none) from the record handed ``trace_record(kind, ...)`` while the step
    program traced (None if none). It also emits the kind's events."""
    def register(report):
        _REPORTS[kind] = report
        return report
    return register


def trace_record(kind: str, record) -> None:
    """Hand ``record`` to the program being traced (``recording``)."""
    records = getattr(_AMBIENT, "records", None)
    if records is not None:
        records[kind] = record


def recording(records: dict):
    """Collect into ``records`` what ``trace_record`` is handed in the block:
    around a jitted function's call, what its trace handed."""
    return _ambient("records", records)


def counters(state) -> dict:
    """``{kind: {layer path: {counter: value}}}`` of the layers in a model's
    ``state`` tree that keep a ``counts`` kind's counters, fetched from the
    device: call it outside a step loop (``Model.fit`` does, at its end)."""
    out: Dict[str, dict] = {}

    def walk(tree, path):
        if not isinstance(tree, dict):
            return
        for kind, (names, _) in _COUNTED.items():
            if all(c in tree for c in names):
                values = jax.device_get({c: tree[c] for c in names})
                out.setdefault(kind, {})["/".join(path)] = {
                    c: float(v) for c, v in values.items()}
                return
        for key, sub in tree.items():
            walk(sub, path + (key,))

    walk(state, ())
    return out


def layer_report(state, records: dict, strategy, registry) -> dict:
    """The layers' entries of a fit's report: each ``reports`` kind's from
    ``records``, each ``counts`` kind's with its gauges set in ``registry``."""
    out = {}
    for kind, report in sorted(_REPORTS.items()):
        entry = report(records.get(kind), strategy)
        if entry is not None:
            out[kind] = entry
    for kind, layers in sorted(counters(state).items()):
        out[kind] = layers
        for name, value in _COUNTED[kind][1](layers).items():
            registry.gauge(name, value)
    return out


def apply_layers(layers, params, state, x, *, train=False, rng=None):
    """Apply a sequence of layers with Sequential's rng-split and state-
    collection discipline. The SINGLE implementation of that discipline:
    Sequential.apply delegates here, and the chunked-head training path
    (training/model.py) applies a Sequential's body (all layers but the
    head) through the same function, so the two can't drift."""
    new_state: State = {}
    n_rng = sum(1 for l in layers if getattr(l, "needs_rng", False))
    rngs = iter(jax.random.split(rng, n_rng)) if (rng is not None and n_rng) else iter(())
    for layer in layers:
        layer_rng = next(rngs, None) if getattr(layer, "needs_rng", False) else None
        with child_scope(layer.name):
            x, s = layer.apply(
                params.get(layer.name, {}),
                state.get(layer.name, {}),
                x,
                train=train,
                rng=layer_rng,
            )
        if s:
            new_state[layer.name] = s
    return x, new_state


class Sequential(Layer):
    """Linear stack of layers; itself a Layer, so stacks compose.

    Parity target: ``keras_model_sequential() %>% layer_conv_2d(...) %>% ...``
    (/root/reference/README.md:58-68) and ``tf.keras.Sequential([...])``
    (/root/reference/README.md:292-298).
    """

    def __init__(self, layers: Sequence[Layer], name: Optional[str] = None):
        super().__init__(name)
        self.layers = list(layers)
        scope = NameScope()
        for layer in self.layers:
            layer.name = scope.assign(layer)

    def add(self, layer: Layer):
        scope = NameScope()
        for existing in self.layers:
            scope._used.add(existing.name)
            m = re.fullmatch(r"(.+?)(?:_(\d+))?", existing.name)
            base = m.group(1) if m else existing.name
            idx = int(m.group(2)) + 1 if m and m.group(2) else 1
            scope._counts[base] = max(scope._counts.get(base, 0), idx)
        layer.name = scope.assign(layer)
        self.layers.append(layer)

    @property
    def needs_rng(self) -> bool:
        # Containers need an rng iff any child does (nested Dropout etc.).
        return any(getattr(l, "needs_rng", False) for l in self.layers)

    def init(self, key, input_shape):
        params: Params = {}
        state: State = {}
        shape = tuple(input_shape)
        keys = jax.random.split(key, max(len(self.layers), 1))
        for layer, k in zip(self.layers, keys):
            p, s, shape = layer.init(k, shape)
            if p:
                params[layer.name] = p
            if s:
                state[layer.name] = s
        return params, state, shape

    def sharding_hints(self):
        hints = {}
        for layer in self.layers:
            h = layer.sharding_hints()
            if h:
                hints[layer.name] = h
        return hints

    def dtype_hints(self):
        hints = {}
        for layer in self.layers:
            h = layer.dtype_hints()
            if h is not None and h != {}:
                hints[layer.name] = h
        return hints

    def apply(self, params, state, x, *, train=False, rng=None):
        return apply_layers(
            self.layers, params, state, x, train=train, rng=rng
        )

    def init_cache(self, params, batch, max_len, dtype):
        caches = {}
        for layer in self.layers:
            c = layer.init_cache(
                params.get(layer.name, {}), batch, max_len, dtype
            )
            if c:
                caches[layer.name] = c
        return caches

    def decode(self, params, state, cache, x, *, pos):
        new_cache = dict(cache)
        for layer in self.layers:
            x, c = layer.decode(
                params.get(layer.name, {}),
                state.get(layer.name, {}),
                cache.get(layer.name, {}),
                x,
                pos=pos,
            )
            if c:
                new_cache[layer.name] = c
        return x, new_cache

    def init_paged_cache(self, params, num_blocks, block_size, dtype):
        caches = {}
        for layer in self.layers:
            c = layer.init_paged_cache(
                params.get(layer.name, {}), num_blocks, block_size, dtype
            )
            if c:
                caches[layer.name] = c
        return caches

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        new_cache = dict(cache)
        for layer in self.layers:
            x, c = layer.paged_decode(
                params.get(layer.name, {}),
                state.get(layer.name, {}),
                cache.get(layer.name, {}),
                x,
                block_tables=block_tables,
                positions=positions,
            )
            if c:
                new_cache[layer.name] = c
        return x, new_cache

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        new_cache = dict(cache)
        for layer in self.layers:
            x, c = layer.paged_verify(
                params.get(layer.name, {}),
                state.get(layer.name, {}),
                cache.get(layer.name, {}),
                x,
                block_tables=block_tables,
                positions=positions,
            )
            if c:
                new_cache[layer.name] = c
        return x, new_cache

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        new_cache = dict(cache)
        for layer in self.layers:
            x, c = layer.paged_prefill(
                params.get(layer.name, {}),
                state.get(layer.name, {}),
                cache.get(layer.name, {}),
                x,
                block_table=block_table,
                start=start,
            )
            if c:
                new_cache[layer.name] = c
        return x, new_cache

    def summary_lines(self, input_shape: Shape):
        """Keras-style summary rows: (name, output_shape, param_count)."""
        from ..utils.tree import tree_size

        rows = []
        key = jax.random.PRNGKey(0)
        shape = tuple(input_shape)
        for layer in self.layers:
            p, _, shape = layer.init(key, shape)
            rows.append((layer.name, (None,) + shape, tree_size(p)))
        return rows

    def __repr__(self):
        inner = ", ".join(repr(l) for l in self.layers)
        return f"Sequential([{inner}])"


class TiedSequential(Sequential):
    """A ``Sequential`` whose last layer owns no leaf and is handed its
    first layer's parameters instead: a language model whose head is its
    embedding's table (``[Embedding, ..., TiedHead]``). A ``Sequential``
    hands each layer its own subtree alone, so tying is the container's
    work, not a layer's. The table stays ONE leaf, under the embedding's
    key: one entry in the optimizer and in a checkpoint, and its gradient
    is the sum of the two uses', which is autodiff's of a leaf read twice.
    The head still runs under its own name as a device scope.

    For training and full forward passes: the decode paths and
    ``compile(head_chunks=...)`` apply a model's layers themselves, each on
    its own subtree, and the head says so when it finds no table there."""

    def init(self, key, input_shape):
        params, state, shape = super().init(key, input_shape)
        head = self.layers[-1]
        if head.name in params:
            raise ValueError(
                f"the tied layer {head.name!r} owns parameters "
                f"({sorted(params[head.name])}); it has to read "
                f"{self.layers[0].name!r}'s alone")
        return params, state, shape

    def apply(self, params, state, x, *, train=False, rng=None):
        tied = {**params,
                self.layers[-1].name: params[self.layers[0].name]}
        return apply_layers(self.layers, tied, state, x, train=train, rng=rng)


class Residual(Layer):
    """Skip connection: ``y = activation(main(x) + shortcut(x))``.

    ``shortcut`` defaults to the identity. This is the non-sequential
    composition primitive the ResNet family needs; both branches are ordinary
    Layers (usually Sequentials), so the whole block still jits into one XLA
    program with static dataflow — the add fuses into the preceding conv's
    epilogue on TPU.
    """

    def __init__(self, main: Layer, shortcut: Optional[Layer] = None,
                 activation=None, name: Optional[str] = None):
        super().__init__(name)
        from . import activations  # local import: core must not cycle

        self.main = main
        self.shortcut = shortcut
        self.activation = activations.get(activation)
        for branch, default in ((main, "main"), (shortcut, "shortcut")):
            if branch is not None and branch.name is None:
                branch.name = default

    @property
    def needs_rng(self) -> bool:
        return any(
            getattr(b, "needs_rng", False)
            for b in (self.main, self.shortcut)
            if b is not None
        )

    def init(self, key, input_shape):
        k1, k2 = jax.random.split(key)
        pm, sm, out_main = self.main.init(k1, tuple(input_shape))
        if self.shortcut is not None:
            ps, ss, out_sc = self.shortcut.init(k2, tuple(input_shape))
        else:
            ps, ss, out_sc = {}, {}, tuple(input_shape)
        if out_main != out_sc:
            raise ValueError(
                f"Residual branch shapes differ: main {out_main} vs "
                f"shortcut {out_sc} (add a projection shortcut)"
            )
        params = {"main": pm}
        state = {"main": sm} if sm else {}
        if ps:
            params["shortcut"] = ps
        if ss:
            state["shortcut"] = ss
        return params, state, out_main

    def sharding_hints(self):
        hints = {}
        h = self.main.sharding_hints()
        if h:
            hints["main"] = h
        if self.shortcut is not None:
            h = self.shortcut.sharding_hints()
            if h:
                hints["shortcut"] = h
        return hints

    def dtype_hints(self):
        hints = {}
        h = self.main.dtype_hints()
        if h is not None and h != {}:
            hints["main"] = h
        if self.shortcut is not None:
            h = self.shortcut.dtype_hints()
            if h is not None and h != {}:
                hints["shortcut"] = h
        return hints

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, 2) if rng is not None else (None, None)
        )
        main_rng = rngs[0] if getattr(self.main, "needs_rng", False) else None
        with child_scope("main"):
            y, sm = self.main.apply(
                params.get("main", {}), state.get("main", {}), x,
                train=train, rng=main_rng,
            )
        if self.shortcut is not None:
            sc_rng = rngs[1] if getattr(self.shortcut, "needs_rng", False) else None
            with child_scope("shortcut"):
                sc, ss = self.shortcut.apply(
                    params.get("shortcut", {}), state.get("shortcut", {}),
                    x, train=train, rng=sc_rng,
                )
        else:
            sc, ss = x, {}
        new_state = {}
        if sm:
            new_state["main"] = sm
        if ss:
            new_state["shortcut"] = ss
        return self.activation(y + sc), new_state

    def init_cache(self, params, batch, max_len, dtype):
        caches = {}
        c = self.main.init_cache(params.get("main", {}), batch, max_len, dtype)
        if c:
            caches["main"] = c
        if self.shortcut is not None:
            c = self.shortcut.init_cache(
                params.get("shortcut", {}), batch, max_len, dtype
            )
            if c:
                caches["shortcut"] = c
        return caches

    def decode(self, params, state, cache, x, *, pos):
        y, cm = self.main.decode(
            params.get("main", {}), state.get("main", {}),
            cache.get("main", {}), x, pos=pos,
        )
        new_cache = dict(cache)
        if cm:
            new_cache["main"] = cm
        if self.shortcut is not None:
            sc, cs = self.shortcut.decode(
                params.get("shortcut", {}), state.get("shortcut", {}),
                cache.get("shortcut", {}), x, pos=pos,
            )
            if cs:
                new_cache["shortcut"] = cs
        else:
            sc = x
        return self.activation(y + sc), new_cache

    def init_paged_cache(self, params, num_blocks, block_size, dtype):
        caches = {}
        c = self.main.init_paged_cache(
            params.get("main", {}), num_blocks, block_size, dtype
        )
        if c:
            caches["main"] = c
        if self.shortcut is not None:
            c = self.shortcut.init_paged_cache(
                params.get("shortcut", {}), num_blocks, block_size, dtype
            )
            if c:
                caches["shortcut"] = c
        return caches

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        y, cm = self.main.paged_decode(
            params.get("main", {}), state.get("main", {}),
            cache.get("main", {}), x,
            block_tables=block_tables, positions=positions,
        )
        new_cache = dict(cache)
        if cm:
            new_cache["main"] = cm
        if self.shortcut is not None:
            sc, cs = self.shortcut.paged_decode(
                params.get("shortcut", {}), state.get("shortcut", {}),
                cache.get("shortcut", {}), x,
                block_tables=block_tables, positions=positions,
            )
            if cs:
                new_cache["shortcut"] = cs
        else:
            sc = x
        return self.activation(y + sc), new_cache

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        y, cm = self.main.paged_verify(
            params.get("main", {}), state.get("main", {}),
            cache.get("main", {}), x,
            block_tables=block_tables, positions=positions,
        )
        new_cache = dict(cache)
        if cm:
            new_cache["main"] = cm
        if self.shortcut is not None:
            sc, cs = self.shortcut.paged_verify(
                params.get("shortcut", {}), state.get("shortcut", {}),
                cache.get("shortcut", {}), x,
                block_tables=block_tables, positions=positions,
            )
            if cs:
                new_cache["shortcut"] = cs
        else:
            sc = x
        return self.activation(y + sc), new_cache

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        y, cm = self.main.paged_prefill(
            params.get("main", {}), state.get("main", {}),
            cache.get("main", {}), x, block_table=block_table, start=start,
        )
        new_cache = dict(cache)
        if cm:
            new_cache["main"] = cm
        if self.shortcut is not None:
            sc, cs = self.shortcut.paged_prefill(
                params.get("shortcut", {}), state.get("shortcut", {}),
                cache.get("shortcut", {}), x,
                block_table=block_table, start=start,
            )
            if cs:
                new_cache["shortcut"] = cs
        else:
            sc = x
        return self.activation(y + sc), new_cache

    def __repr__(self):
        return (
            f"Residual(main={self.main!r}, shortcut={self.shortcut!r})"
        )


class Lambda(Layer):
    """Wrap an arbitrary stateless function ``fn(x) -> y``."""

    # The wrapped fn is opaque — it may mix positions (e.g. a reduction
    # over the time axis), so one-token decode cannot be assumed exact.
    decode_safe = False

    def __init__(self, fn, output_shape=None, name=None):
        super().__init__(name)
        self.fn = fn
        self._output_shape = output_shape

    def init(self, key, input_shape):
        if self._output_shape is not None:
            out = tuple(self._output_shape)
        else:
            out = jax.eval_shape(self.fn, jax.ShapeDtypeStruct((1,) + tuple(input_shape), "float32")).shape[1:]
        return {}, {}, out

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.fn(x), {}
