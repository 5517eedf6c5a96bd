"""Distribution strategies.

Parity target: ``tf.distribute.experimental.MultiWorkerMirroredStrategy()``
and its ``strategy.scope()`` UX (/root/reference/README.md:122, 134-151,
364-386). The contract preserved here:

- *Scope-wraps-construction*: a ``Model`` built inside ``strategy.scope()``
  is distributed; the local script and the distributed script differ by a few
  lines (SURVEY.md §3.4: "local -> distributed is a ~6-line diff").
- *Config-by-environment*: constructing ``DataParallel()`` with no arguments
  discovers the device/process topology (from `jax.devices()` and, multi-host,
  from the cluster bootstrap in `distributed_tpu.cluster`), the way the
  reference's strategy reads TF_CONFIG implicitly.

Mechanically it is nothing like the reference: there is no gRPC server, no
DistributeCoordinator, no mirrored-variable objects. Parameters are placed
with a replicated ``NamedSharding`` over a mesh, batches are sharded on the
'data' axis, and the per-step gradient all-reduce the reference gets from its
C++ CollectiveAllReduce kernels (/root/reference/README.md:403) is emitted by
XLA as a fused collective over ICI when jit partitions the train step.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import make_mesh

_local = threading.local()


def current_strategy() -> Optional["Strategy"]:
    return getattr(_local, "strategy", None)


def _put_global(x, sh: NamedSharding):
    """Place one host-global array under `sh` (the single implementation
    every strategy's put_batch delegates to). Every process holds the full
    host batch (the reference's full-dataset-everywhere feeding,
    /root/reference/README.md:369-373), so multi-host placement serves each
    addressable shard by slicing the local copy — correct for ANY sharding,
    including axes (seq, model) that span processes, not just row slices."""
    x = np.asarray(x)
    if jax.process_count() > 1:
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])
    return jax.device_put(x, sh)


def _largest_divisible_spec(shape, n: int, axis: str,
                            taken=None) -> PartitionSpec:
    """ZeRO placement rule shared by every sharded-state strategy: shard the
    largest dimension divisible by the axis size ``n``; replicate scalars and
    awkward shapes (they're small). ``taken``: per-dim entries already
    assigned to other mesh axes (kept, never double-sharded)."""
    spec = list(taken) if taken is not None else [None] * len(shape)
    best, best_size = None, 0
    for d, size in enumerate(shape):
        if spec[d] is None and size % n == 0 and size > best_size:
            best, best_size = d, size
    if best is not None:
        spec[best] = axis
    if all(s is None for s in spec):
        return PartitionSpec()  # fully replicated, canonical spelling
    return PartitionSpec(*spec)


def _path_key(entry) -> str:
    """Stable name of one tree-path entry (DictKey / SequenceKey /
    GetAttrKey / FlattenedIndexKey all stringify distinctly)."""
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _params_sharding_tree(strategy, params, hints=None):
    """``strategy.params_sharding(params[, hints])`` across the two
    signatures in this module (the base/DP family takes no hints; the
    hinted family does). Shared by opt_state_sharding and the planner."""
    try:
        return strategy.params_sharding(params, hints)
    except TypeError:
        return strategy.params_sharding(params)


class Strategy:
    """Base strategy: knows the mesh and how to place params and batches."""

    mesh: Optional[Mesh] = None

    @property
    def num_replicas_in_sync(self) -> int:
        return 1

    @contextlib.contextmanager
    def scope(self):
        prev = current_strategy()
        _local.strategy = self
        try:
            yield self
        finally:
            _local.strategy = prev

    # -- placement ----------------------------------------------------------
    def params_sharding(self, params):
        """Sharding pytree for params/opt-state (None = let jit decide)."""
        return None

    def batch_sharding(self):
        return None

    def put_params(self, params, hints=None):
        """Place a params-like pytree. ``hints`` is the module's nested
        tensor-parallel role tree (nn.Layer.sharding_hints); strategies
        without a model axis ignore it."""
        return params

    def init_opt_state(self, tx, params):
        """Optimizer state placed consistently with the params."""
        return self.put_params(tx.init(params))

    def constrain_step(self, params, opt_state):
        """Trace-time sharding constraints on a train step's updated
        (params, opt_state), applied inside the jitted step after the
        optimizer update. The default pins nothing — GSPMD's propagation
        is already unambiguous when params and optimizer state share one
        placement. Strategies that MIX placements (ZeRO: replicated params
        next to sharded optimizer state) override this to pin each output
        to its intended layout; otherwise propagation is free to leak the
        optimizer's sharding into the updated params (or vice versa),
        silently changing the layout — and the compiled program — from
        step 2 on."""
        return params, opt_state

    def constrain_compute_params(self, params):
        """Trace-time hook on the COMPUTE-DTYPE copy of the params a mixed-
        precision step builds (``Policy.cast_to_compute`` inside the jitted
        body). Strategies that shard params (FSDP family) pin the cast copy
        to the SAME shard layout as the f32 masters, so the per-layer
        all-gathers GSPMD inserts happen AFTER the cast and move
        compute-dtype bytes — under bf16 that halves the dominant FSDP
        collective. Identity by default (replicated params gather
        nothing)."""
        return params

    def overlap_spec(self):
        """Comm/compute-overlap seam for the per-layer scan
        (``nn.ScannedBlocks``). Strategies whose parameters are SHARDED
        and gathered per layer (FSDP family) return a gather callable —
        one layer's (sharded) param slice -> the same tree constrained to
        a fully replicated layout, i.e. an explicit all-gather the scan
        body can issue one layer AHEAD of use, so layer i+1's gather has
        no data dependency on layer i's compute and the scheduler can
        overlap the two (the collective-matmul idiom). Composes with
        ``constrain_compute_params`` and the precision cast: the slice
        arriving at the gather is already the compute-dtype shard copy,
        so bf16 moves on the wire. ``None`` (default) = params are
        already resident per device; the scan keeps its plain body."""
        return None

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        """Analytic per-step, per-device collective-traffic estimate for
        the parameter-sized collectives this strategy emits, at the dtype
        the bytes actually move in (``compute_dtype`` under a mixed-
        precision policy, else the leaves' own dtype — int8 weight-only
        leaves (quant.py) keep their 1-byte dtype under EVERY strategy).
        The schema is UNIFIED across SingleDevice/DP/ZeRO-1/FSDP/TP
        (zeros where a collective doesn't apply) so the auto-shard
        planner can compare rows apples-to-apples. Keys:

        - ``gathered_param_bytes_per_device``: one full gather of the
          strategy's sharded parameter state per step (FSDP: the per-layer
          forward all-gather, repeated for backward but counted once so
          the number stays a comparable "bytes of one gather"; ZeRO-1: the
          post-update all-gather of the parameter updates, at MASTER dtype
          — the update applies to f32 params).
        - ``grad_reduce_bytes_per_device``: the gradient all-reduce /
          reduce-scatter, one param-tree's worth of bytes (of the bytes
          this device HOLDS — a TP-sharded leaf reduces shard-sized
          pieces).
        - ``activation_reduce_bytes_per_token_per_device``: Megatron-style
          per-layer activation all-reduces, PER TOKEN (they scale with the
          batch the params estimate can't see; multiply by the step's
          local token count). Non-zero only for tensor-parallel
          strategies, which need ``hints`` (the module's sharding-role
          tree) to know which matmuls are sharded.

        ``params`` may be a live tree or abstract ``ShapeDtypeStruct``
        leaves (the planner's dry-run path). An estimate, not a
        measurement (ring-collective (N-1)/N factors and XLA fusion are
        ignored): its job is to make traffic RATIOS across configs/dtypes
        visible in telemetry and the planner, which those constant factors
        cancel out of. Base strategy emits no collectives."""
        return self._comm_row()

    @staticmethod
    def _comm_row(gathered=0, grad=0, act_per_token=0,
                  pipeline_hop_per_token=0) -> dict:
        """The unified comm_bytes_estimate schema — one constructor so
        strategies cannot drift keys. ``pipeline_hop_per_token``: bytes of
        microbatch activations a pipeline schedule ppermutes per token per
        device per step (zero for every non-pipeline strategy — the key
        exists on all rows so consumers never branch on presence)."""
        return {
            "gathered_param_bytes_per_device": int(gathered),
            "grad_reduce_bytes_per_device": int(grad),
            "activation_reduce_bytes_per_token_per_device": int(
                act_per_token
            ),
            "pipeline_hop_bytes_per_token_per_device": int(
                pipeline_hop_per_token
            ),
        }

    def opt_state_sharding(self, opt_state, params, hints=None):
        """Sharding tree for an optimizer-state pytree, mirroring what
        ``init_opt_state`` produces EAGERLY — but computable on abstract
        ``ShapeDtypeStruct`` trees (the auto-shard planner prices
        optimizer memory without materializing it). Default rule matches
        the eager inherit-from-params behavior: an optimizer stat whose
        tree-path tail + shape matches a parameter (Adam's mu/nu, SGD
        momentum — optax stats mirror the params nesting) gets that
        parameter's sharding; everything else (step counters, injected
        hyperparams) replicates. Strategies with bespoke optimizer
        placement (ZeRO-1's largest-divisible-dim shards) override."""
        psh = _params_sharding_tree(self, params, hints)
        if psh is None:
            return jax.tree_util.tree_map(lambda _: None, opt_state)
        rep = (
            NamedSharding(self.mesh, PartitionSpec())
            if self.mesh is not None else None
        )
        index = {}
        param_leaves = jax.tree_util.tree_leaves_with_path(params)
        for (path, leaf), sh in zip(
            param_leaves, jax.tree_util.tree_leaves(psh)
        ):
            names = tuple(_path_key(k) for k in path)
            index[(names, tuple(leaf.shape))] = sh

        def place(path, leaf):
            names = tuple(_path_key(k) for k in path)
            shape = tuple(getattr(leaf, "shape", ()))
            for i in range(len(names)):
                hit = index.get((names[i:], shape))
                if hit is not None:
                    return hit
            return rep

        return jax.tree_util.tree_map_with_path(place, opt_state)

    @staticmethod
    def _leaf_comm_bytes(leaf, compute_dtype=None) -> int:
        """Bytes one parameter leaf contributes to a collective when moved
        at ``compute_dtype`` (floating leaves only; others keep their own
        dtype — in particular int8 weight-only payloads (quant.py) are
        priced at 1 byte/elem, which is how the 4x-vs-f32 / 2x-vs-bf16
        gather savings of quantized serving show up in this estimate)."""
        import jax.numpy as jnp

        size = int(np.prod(leaf.shape)) if getattr(leaf, "shape", None) else 1
        dt = jnp.result_type(leaf)
        if compute_dtype is not None and jnp.issubdtype(dt, jnp.floating):
            dt = jnp.dtype(compute_dtype)
        return size * jnp.dtype(dt).itemsize

    def put_batch(self, batch, per_host: bool = False,
                  stacked: bool = False, async_: bool = False):
        """Place a numpy batch onto devices. ``per_host=True`` means each
        process passes only ITS row-shard of the global batch (from e.g. a
        sharded ``data.Pipeline``); the shards assemble into one global
        array. Default is host-global input (every process passes the full
        batch, the reference's feeding model).

        ``stacked=True``: the batch is a ``[K, batch, ...]`` super-batch
        (``Model.compile(steps_per_execution=K)``) — the leading K axis is
        replicated and the SECOND axis is the batch axis: every sharding
        rule shifts one dimension right, so one transfer stages K steps of
        data exactly as K separate ``put_batch`` calls would have.

        ``async_=True``: the caller is a background prefetch stage
        (``data.DevicePrefetcher``) staging dispatch N+1 while dispatch N
        runs — the call MUST only *start* the host->device transfer
        (non-blocking ``jax.device_put``) and must never synchronize
        (``block_until_ready``, ``device_get``) or run a collective. Every
        strategy's placement already satisfies this; the flag is the
        contract that keeps any future implementation honest, and the
        hook under which one could route placement through a dedicated
        transfer stream."""
        if per_host:
            raise ValueError(
                f"{type(self).__name__} cannot assemble per-host input "
                "shards; use an unsharded data source, or a strategy with "
                "a batch axis (DataParallel family)"
            )
        return batch

    def local_batch_size(self, global_batch: int) -> int:
        return global_batch


class SingleDevice(Strategy):
    """No distribution: plain jit on the default device (the reference's local
    smoke-test path, /root/reference/README.md:45-76, 281-312)."""

    def __init__(self, device: Optional[jax.Device] = None):
        self.device = device or jax.devices()[0]

    def put_batch(self, batch, per_host: bool = False,
                  stacked: bool = False, async_: bool = False):
        # stacked super-batches need no special placement on one device;
        # device_put is already non-blocking, satisfying async_.
        if per_host:
            raise ValueError(
                "SingleDevice cannot assemble per-host input shards; a "
                "sharded data.Pipeline would silently train on a fraction "
                "of each batch. Use shard=None, or build the model under a "
                "DataParallel-family strategy scope"
            )
        return jax.device_put(batch, self.device)

    def put_params(self, params, hints=None):
        return jax.device_put(params, self.device)


class DataParallel(Strategy):
    """Synchronous all-reduce data parallelism over a named mesh axis.

    Equivalent capability to MultiWorkerMirroredStrategy
    (/root/reference/README.md:122): params replicated, global batch split
    across replicas (64 per replica x N replicas in the reference,
    README.md:124-125), gradients summed every step. Collectives ride ICI
    (and DCN across slices) because they are XLA-emitted, not gRPC.
    """

    def __init__(self, devices=None, *, mesh: Optional[Mesh] = None, axis: str = "data"):
        if mesh is not None:
            self.mesh = mesh
        else:
            self.mesh = make_mesh({axis: len(devices or jax.devices())}, devices=devices)
        self.axis = axis
        if axis not in self.mesh.axis_names:
            raise ValueError(f"Mesh {self.mesh.axis_names} has no axis {axis!r}")

    @property
    def num_replicas_in_sync(self) -> int:
        # Only the batch axis counts: on a multi-axis mesh (e.g. data x model)
        # the other axes shard the model, not the batch.
        return int(self.mesh.shape[self.axis])

    @property
    def row_axes(self) -> tuple:
        """Mesh axes the batch's row (leading) dim shards over. Consumers
        outside this module (nn.PipelinedBlocks) read this instead of any
        private attribute."""
        return (self.axis,)

    def params_sharding(self, params):
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.tree_util.tree_map(lambda _: rep, params)

    def batch_sharding(self):
        return NamedSharding(self.mesh, PartitionSpec(self.axis))

    def put_params(self, params, hints=None):
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.device_put(params, rep)

    def put_batch(self, batch, per_host: bool = False,
                  stacked: bool = False, async_: bool = False):
        """Place a batch. Host-global by default (same array on every
        process, like the reference's full-dataset-everywhere feeding,
        /root/reference/README.md:369-373, with each process device-putting
        only its addressable slices). ``per_host=True``: each process passes
        only its own row-shard (rows [i*b/P, (i+1)*b/P) of the global batch,
        e.g. from ``data.Pipeline(shard=(i, P))``) and never materializes
        the rest (SURVEY.md §7 hard parts). ``stacked=True``: leading-K
        super-batch — K replicated, rows (dim 1) sharded (see
        Strategy.put_batch)."""
        sh = self.batch_sharding()
        if stacked:
            sh = NamedSharding(self.mesh, PartitionSpec(None, self.axis))
        if per_host:
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    sh, np.asarray(x)
                ),
                batch,
            )
        return jax.tree_util.tree_map(lambda x: _put_global(x, sh), batch)

    def local_batch_size(self, global_batch: int) -> int:
        n = self.num_replicas_in_sync
        if global_batch % n:
            raise ValueError(
                f"Global batch {global_batch} not divisible by {n} replicas"
            )
        return global_batch // n

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        # Replicated DP: one gradient all-reduce of the full param tree per
        # step; the cotangents it moves are compute-dtype under a mixed
        # policy (the f32 cast-back to masters happens per device). Int8
        # weight-only leaves keep their 1-byte dtype (_leaf_comm_bytes).
        grad = sum(
            self._leaf_comm_bytes(l, compute_dtype)
            for l in jax.tree_util.tree_leaves(params)
        )
        return self._comm_row(grad=grad)


class ZeroDataParallel(DataParallel):
    """ZeRO-1 data parallelism: params replicated, optimizer state sharded
    over the 'data' axis (Rajbhandari et al., 2020, stage 1 — expressed as
    NamedShardings the GSPMD way, Xu et al., 2021).

    The forward/backward is bit-identical to ``DataParallel`` (same batch
    sharding, same gradient all-reduce); only the optimizer update is
    partitioned: each device keeps 1/N of every Adam/momentum statistic on
    its largest divisible dim, computes its slice of the parameter update,
    and XLA all-gathers the updates back onto the replicated params. Per-
    device optimizer memory drops from O(params x stats) to O(params x
    stats / N) — with Adam that cuts total model state from ~3x params to
    ~(1 + 2/N)x — at the cost of one all-gather of update-sized data per
    step, which rides the same ICI links as the gradient all-reduce.
    Checkpoints are strategy-portable: save gathers full leaves, restore
    re-places under the live strategy (checkpoint/core.py).
    """

    def _opt_spec(self, shape) -> PartitionSpec:
        return _largest_divisible_spec(
            shape, int(self.mesh.shape[self.axis]), self.axis
        )

    def _shardable(self, a) -> bool:
        # In-trace (constrain_step) and eager (init) leaves both expose
        # shape/ndim; python scalars and 0-d leaves stay replicated.
        return getattr(a, "ndim", 0) >= 1

    def init_opt_state(self, tx, params):
        opt = super().init_opt_state(tx, params)  # eager init, replicated
        rep_spec = PartitionSpec()

        def place(a):
            if not self._shardable(a):
                return a
            spec = self._opt_spec(a.shape)
            if spec == rep_spec:
                return a
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(place, opt)

    def constrain_step(self, params, opt_state):
        rep = NamedSharding(self.mesh, PartitionSpec())
        params = jax.tree_util.tree_map(
            lambda p: jax.lax.with_sharding_constraint(p, rep), params
        )
        opt_state = jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, self._opt_spec(a.shape))
            ) if self._shardable(a) else a,
            opt_state,
        )
        return params, opt_state

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        # DP's gradient all-reduce (compute-dtype bytes under a mixed
        # policy) plus ZeRO-1's post-update all-gather of the parameter
        # updates — which applies to the f32 MASTERS, so those bytes do
        # NOT shrink under a reduced compute dtype (int8 leaves still
        # price at their own 1-byte dtype).
        out = super().comm_bytes_estimate(params, compute_dtype, hints)
        out["gathered_param_bytes_per_device"] = sum(
            self._leaf_comm_bytes(l, None)
            for l in jax.tree_util.tree_leaves(params)
            if self._shardable(l) and self._opt_spec(l.shape) != PartitionSpec()
        )
        return out

    def opt_state_sharding(self, opt_state, params, hints=None):
        # Mirrors init_opt_state: every ndim>=1 stat shards on its largest
        # divisible dim; scalars replicate.
        rep = NamedSharding(self.mesh, PartitionSpec())

        def place(a):
            if not self._shardable(a):
                return rep
            return NamedSharding(self.mesh, self._opt_spec(a.shape))

        return jax.tree_util.tree_map(place, opt_state)


def _check_pipe_divisible(params, hints, n: int, axis_name: str):
    """Fail with a framework-level message before device_put trips over an
    indivisible pipelined stage stack."""

    def check(p, h):
        if isinstance(p, dict):
            for k, v in p.items():
                check(v, h.get(k, {}) if isinstance(h, dict) else h)
        elif h == "pipe" and p.shape[0] % n:
            raise ValueError(
                f"{p.shape[0]} pipelined blocks not divisible by "
                f"{axis_name}={n} stages"
            )

    check(params, hints or {})


def _put_batch_rows_seq(mesh: Mesh, rows, seq_axis: Optional[str], batch,
                        per_host: bool, stacked: bool = False):
    """Shared batch placement for strategies with row sharding and an
    optional sequence axis (DataSeqParallel, CompositeParallel): rows shard
    over ``rows`` (one axis name or a tuple), dim 1 over ``seq_axis`` when
    present and the leaf has one. ``stacked``: leading [K] multi-step dim,
    replicated; every other rule shifts one dimension right."""
    lead = (None,) if stacked else ()
    row_dim = len(lead)

    def _put(x):
        x = np.asarray(x)
        if seq_axis and x.ndim >= row_dim + 2:
            seq_len = x.shape[row_dim + 1]
            n_seq = int(mesh.shape[seq_axis])
            if seq_len % n_seq:
                raise ValueError(
                    f"sequence length {seq_len} not divisible by "
                    f"{seq_axis}={n_seq} shards"
                )
            spec = PartitionSpec(
                *lead, rows, seq_axis, *([None] * (x.ndim - row_dim - 2))
            )
        else:
            spec = PartitionSpec(*lead, rows)
        sh = NamedSharding(mesh, spec)
        if per_host:
            # A per-host row shard carries the FULL sequence, which only
            # maps onto this process's addressable shards when no seq
            # split crosses a process boundary.
            if (
                seq_axis
                and x.ndim >= row_dim + 2
                and _axis_spans_processes(mesh, seq_axis)
            ):
                raise ValueError(
                    "per-host sharded input is unsupported when the "
                    f"'{seq_axis}' axis spans processes: each process "
                    "would also need to pre-slice its sequence shard. "
                    "Feed host-global batches instead"
                )
            return jax.make_array_from_process_local_data(sh, x)
        return _put_global(x, sh)

    return jax.tree_util.tree_map(_put, batch)


def _axis_spans_processes(mesh: Mesh, axis: str) -> bool:
    """True when devices along `axis` belong to more than one process (so a
    per-host row-shard can't carry full rows along that axis)."""
    devs = mesh.devices
    dim = mesh.axis_names.index(axis)
    moved = np.moveaxis(devs, dim, -1).reshape(-1, devs.shape[dim])
    for line in moved:
        if len({d.process_index for d in line}) > 1:
            return True
    return False


class _HintedParallel(DataParallel):
    """Shared machinery for strategies that translate layer sharding hints
    (nn.Layer.sharding_hints role strings) into NamedShardings. Subclasses
    define ``_role_spec(role, shape)``."""

    def _role_spec(self, role: Optional[str], shape) -> PartitionSpec:
        raise NotImplementedError

    def params_sharding(self, params, hints=None):
        def walk(p, h):
            if isinstance(p, dict):
                # A string role at container level applies to the whole
                # subtree (e.g. PipelinedBlocks marks its stacked params
                # {"blocks": "pipe"}).
                return {
                    k: walk(v, h.get(k, {}) if isinstance(h, dict) else h)
                    for k, v in p.items()
                }
            role = h if isinstance(h, str) else None
            return NamedSharding(self.mesh, self._role_spec(role, p.shape))

        return walk(params, hints or {})

    def put_params(self, params, hints=None):
        if hints:
            return jax.device_put(params, self.params_sharding(params, hints))
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.device_put(params, rep)

    def init_opt_state(self, tx, params):
        # Eager init: zeros_like/stat tensors inherit each parameter's
        # NamedSharding directly (a jitted init would lose it — the outputs
        # have no value dependence on the inputs, so GSPMD unpins them).
        # Leaves created from scratch (step counters etc.) get replicated.
        opt = tx.init(params)
        rep = NamedSharding(self.mesh, PartitionSpec())

        def place(a):
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
                return a
            return jax.device_put(a, rep)

        return jax.tree_util.tree_map(place, opt)


class DataTensorParallel(_HintedParallel):
    """2-axis parallelism: batch sharded over 'data', weight matrices of
    hinted layers (Dense(shard=...), MultiHeadAttention) Megatron-sharded
    over 'model'.

    Beyond the reference (whose only strategy is mirrored DP, SURVEY.md
    §2c); built on the same mesh so DP remains the degenerate case — the
    design requirement that TP "compose later" made concrete. The sharded
    matmuls and their all-reduces are emitted by XLA from the parameter
    NamedShardings; there is no hand-written collective code.
    """

    def __init__(
        self,
        devices=None,
        *,
        mesh: Optional[Mesh] = None,
        model_parallel: int = 2,
        axis: str = "data",
        model_axis: str = "model",
    ):
        if mesh is None:
            ndev = len(devices or jax.devices())
            if ndev % model_parallel:
                raise ValueError(
                    f"{ndev} devices not divisible by model_parallel="
                    f"{model_parallel}"
                )
            mesh = make_mesh(
                {axis: ndev // model_parallel, model_axis: model_parallel},
                devices=devices,
            )
        super().__init__(mesh=mesh, axis=axis)
        if model_axis not in mesh.axis_names:
            raise ValueError(
                f"Mesh {mesh.axis_names} has no axis {model_axis!r}"
            )
        self.model_axis = model_axis

    def _role_spec(self, role: Optional[str], shape) -> PartitionSpec:
        m = self.model_axis
        ndim = len(shape)
        if role == "col":  # shard output/features dim (last)
            return PartitionSpec(*([None] * (ndim - 1) + [m]))
        if role == "row":  # shard input dim (first)
            return PartitionSpec(*([m] + [None] * (ndim - 1)))
        if role == "row1" and ndim >= 2:
            # 'row' behind a stacked leading dim (ScannedBlocks): dim 0 is
            # the block-stack index, the sharded input dim is dim 1.
            return PartitionSpec(*([None, m] + [None] * (ndim - 2)))
        return PartitionSpec()

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        """Megatron TP traffic. Gradient all-reduce over 'data' moves the
        bytes each device HOLDS: full leaves for replicated params, a
        1/model_parallel shard for col/row-hinted ones (without ``hints``
        the estimate degenerates to DP's — it cannot know which leaves
        are sharded). The per-layer activation collectives Megatron adds
        (forward all-reduce after each row-parallel matmul, its mirror in
        backward) scale with the token count, so they are priced PER
        TOKEN: 2 x width-of-each-row-output x compute itemsize — the
        planner multiplies by the step's local tokens. Sharded matmuls
        never gather their weights, so the gathered key stays 0."""
        import jax.numpy as jnp

        tp = int(self.mesh.shape[self.model_axis])
        data = int(self.mesh.shape[self.axis])
        grad = 0
        act_per_token = 0

        def walk(p, h):
            nonlocal grad, act_per_token
            if isinstance(p, dict):
                for k, v in p.items():
                    walk(v, h.get(k, {}) if isinstance(h, dict) else h)
                return
            role = h if isinstance(h, str) else None
            nbytes = self._leaf_comm_bytes(p, compute_dtype)
            sharded = (
                tp > 1
                and self._role_spec(role, p.shape) != PartitionSpec()
            )
            if data > 1:
                grad += nbytes // tp if sharded else nbytes
            if role in ("row", "row1") and tp > 1:
                # Row-parallel output width (last dim; 'row1' stacks
                # shape[0] blocks of it): one fwd + one bwd all-reduce of
                # (tokens, width) activations per block, at compute dtype.
                itemsize = jnp.dtype(
                    compute_dtype
                    if compute_dtype is not None else jnp.result_type(p)
                ).itemsize
                width = int(p.shape[-1])
                stack = int(p.shape[0]) if role == "row1" else 1
                act_per_token += 2 * stack * width * itemsize

        walk(params, hints or {})
        return self._comm_row(grad=grad, act_per_token=act_per_token)


class DataExpertParallel(_HintedParallel):
    """Expert parallelism composed with data parallelism: MoE expert stacks
    (nn.MoE's (E, ...) parameters, hint role 'expert') shard dim 0 over the
    'expert' mesh axis while the batch shards over 'data'. GSPMD lowers the
    dispatch/combine einsums to all-to-alls over ICI. Dense (non-expert)
    params stay replicated. Not in the reference (SURVEY.md §2c "EP: NO").
    """

    def __init__(
        self,
        devices=None,
        *,
        mesh: Optional[Mesh] = None,
        expert_parallel: int = 2,
        axis: str = "data",
        expert_axis: str = "expert",
    ):
        if mesh is None:
            ndev = len(devices or jax.devices())
            if ndev % expert_parallel:
                raise ValueError(
                    f"{ndev} devices not divisible by expert_parallel="
                    f"{expert_parallel}"
                )
            mesh = make_mesh(
                {axis: ndev // expert_parallel, expert_axis: expert_parallel},
                devices=devices,
            )
        super().__init__(mesh=mesh, axis=axis)
        if expert_axis not in mesh.axis_names:
            raise ValueError(
                f"Mesh {mesh.axis_names} has no axis {expert_axis!r}"
            )
        self.expert_axis = expert_axis

    def _role_spec(self, role: Optional[str], shape) -> PartitionSpec:
        if role == "expert":  # shard the expert stack (dim 0)
            return PartitionSpec(
                *([self.expert_axis] + [None] * (len(shape) - 1))
            )
        return PartitionSpec()


class FullyShardedDataParallel(_HintedParallel):
    """ZeRO-3-style fully sharded data parallelism over the 'fsdp' axis.

    Every parameter (and its optimizer state) is sharded across the axis on
    its largest divisible dimension, so per-device parameter memory is
    O(total/n) instead of O(total); the batch is sharded on the same axis.
    XLA's GSPMD inserts the all-gathers before each layer's use and
    reduce-scatters the gradients back to the shards — the behavior DeepSpeed
    ZeRO-3/PyTorch FSDP hand-implement, obtained here from sharding
    annotations alone. Not in the reference (params mirrored, SURVEY.md §2c
    "FSDP / ZeRO: NO"); this is the scale-out axis for models that don't fit
    a chip.
    """

    def __init__(self, devices=None, *, mesh: Optional[Mesh] = None,
                 axis: str = "fsdp"):
        if mesh is None:
            mesh = make_mesh(
                {axis: len(devices or jax.devices())}, devices=devices
            )
        super().__init__(mesh=mesh, axis=axis)

    def _spec_for(self, shape) -> PartitionSpec:
        return _largest_divisible_spec(
            shape, int(self.mesh.shape[self.axis]), self.axis
        )

    def params_sharding(self, params, hints=None):
        return jax.tree_util.tree_map(
            lambda a: NamedSharding(self.mesh, self._spec_for(a.shape)),
            params,
        )

    def put_params(self, params, hints=None):
        return jax.device_put(params, self.params_sharding(params))
    # init_opt_state inherited from _HintedParallel (eager init: stats
    # inherit their parameter's sharding, fresh scalars replicate).

    def constrain_step(self, params, opt_state):
        """Pin updated params AND optimizer state to the per-shape ZeRO
        spec: every placement here is a pure function of the leaf's shape,
        so the constraint is reconstructable on tracers and keeps the
        layout fixed across steps instead of relying on propagation."""
        def pin(a):
            if getattr(a, "ndim", 0) < 1:
                return a
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, self._spec_for(a.shape))
            )

        return (
            jax.tree_util.tree_map(pin, params),
            jax.tree_util.tree_map(pin, opt_state),
        )

    def constrain_compute_params(self, params):
        """Pin the compute-dtype param copy to the SAME per-shape ZeRO
        shard spec as the f32 masters. Without the pin, GSPMD is free to
        gather the f32 masters first and cast afterwards; with it, the
        f32->compute cast runs shard-local and the per-layer all-gathers
        move compute-dtype bytes — half the FSDP traffic under bf16."""
        def pin(a):
            if getattr(a, "ndim", 0) < 1:
                return a
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, self._spec_for(a.shape))
            )

        return jax.tree_util.tree_map(pin, params)

    def overlap_spec(self):
        """FSDP's per-layer gather, made explicit for the scan's
        double-buffered prefetch: pin every ndim>=1 leaf of a layer slice
        to the fully replicated layout (``PartitionSpec()``) — exactly
        the all-gather GSPMD would insert at first use, but issued where
        the scan body says, one layer early. Values are untouched
        (``with_sharding_constraint`` is layout-only and differentiable:
        the backward re-shards the cotangent), so overlapped and plain
        scans are numerically identical."""
        rep = NamedSharding(self.mesh, PartitionSpec())

        def gather(layer_params):
            def pin(a):
                if getattr(a, "ndim", 0) < 1:
                    return a
                return jax.lax.with_sharding_constraint(a, rep)

            return jax.tree_util.tree_map(pin, layer_params)

        return gather

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        # ZeRO-3: every sharded parameter is all-gathered before use (one
        # full gather counted; the backward re-gather doubles it in
        # practice) and the gradients reduce-scatter back — both at
        # compute dtype under a mixed policy, which is THE mixed-precision
        # comms win this estimate exists to expose. Int8 weight-only
        # leaves (quant.py) keep their 1-byte dtype through the
        # compute_dtype override, so a quantized serving tree reports the
        # 4x/2x smaller gathers directly (tests/test_quant.py).
        gathered = sum(
            self._leaf_comm_bytes(l, compute_dtype)
            for l in jax.tree_util.tree_leaves(params)
            if getattr(l, "ndim", 0) >= 1
            and self._spec_for(l.shape) != PartitionSpec()
        )
        grad = sum(
            self._leaf_comm_bytes(l, compute_dtype)
            for l in jax.tree_util.tree_leaves(params)
        )
        return self._comm_row(gathered=gathered, grad=grad)

    def opt_state_sharding(self, opt_state, params, hints=None):
        # Mirrors constrain_step's rule exactly: every ndim>=1 leaf pins to
        # its per-shape ZeRO spec, scalars replicate.
        rep = NamedSharding(self.mesh, PartitionSpec())

        def place(a):
            if getattr(a, "ndim", 0) < 1:
                return rep
            return NamedSharding(self.mesh, self._spec_for(a.shape))

        return jax.tree_util.tree_map(place, opt_state)


class FSDP(FullyShardedDataParallel):
    """ZeRO-3-style fully sharded data parallelism over the **'data'** axis.

    Same mechanics as ``FullyShardedDataParallel`` (params + optimizer
    state sharded on each tensor's largest divisible dim; XLA all-gathers
    params per use and reduce-scatters gradients back to the shards), but
    the shard axis IS the batch axis — the standard ZeRO-3/FSDP recipe
    where one device group provides both data parallelism and parameter
    sharding, so the whole mesh contributes to a single sharded replica.
    Per-device model state is O(params x stats / N): with Adam, ~3x params
    replicated drops to ~3x/N — the axis that trains models which OOM
    under replication (tests/test_zero.py pins the 1/N ratio).

    Compared side by side:

    - ``DataParallel``:       params 1x,   opt 1x per device
    - ``ZeroDataParallel``:   params 1x,   opt 1/N per device (ZeRO-1)
    - ``FSDP``:               params 1/N,  opt 1/N per device (ZeRO-3)

    For hybrids (fsdp x tensor parallel, fsdp as one axis of several) use
    ``CompositeParallel`` — this class is the single-axis form.
    """

    def __init__(self, devices=None, *, mesh: Optional[Mesh] = None,
                 axis: str = "data"):
        super().__init__(devices, mesh=mesh, axis=axis)


class DataPipelineParallel(_HintedParallel):
    """Pipeline parallelism composed with data parallelism.

    A model's ``nn.PipelinedBlocks`` stack shards one-stage-per-rank over the
    'pipe' mesh axis (hint role 'pipe' = leading stage dim) and executes the
    GPipe microbatch schedule inside the jitted train step (see
    nn/pipeline.py); the batch shards over 'data'. Non-pipelined params
    (embeddings, the LM head) stay replicated and compute redundantly on
    every pipe rank — activation hops ride ICI via ppermute, and the reverse
    schedule falls out of jax.grad. Not in the reference (single model
    replica per worker, SURVEY.md §2c "PP: NO").

    ``num_microbatches`` (default: pipe size) trades bubble fraction
    (n-1)/(M+n-1) against per-microbatch MXU efficiency.
    """

    def __init__(
        self,
        devices=None,
        *,
        mesh: Optional[Mesh] = None,
        pipeline_parallel: int = 2,
        num_microbatches: Optional[int] = None,
        axis: str = "data",
        pipe_axis: str = "pipe",
    ):
        if mesh is None:
            ndev = len(devices or jax.devices())
            if ndev % pipeline_parallel:
                raise ValueError(
                    f"{ndev} devices not divisible by pipeline_parallel="
                    f"{pipeline_parallel}"
                )
            mesh = make_mesh(
                {axis: ndev // pipeline_parallel, pipe_axis: pipeline_parallel},
                devices=devices,
            )
        super().__init__(mesh=mesh, axis=axis)
        if pipe_axis not in mesh.axis_names:
            raise ValueError(f"Mesh {mesh.axis_names} has no axis {pipe_axis!r}")
        self.pipe_axis = pipe_axis
        if num_microbatches is None:
            num_microbatches = int(mesh.shape[pipe_axis])
        if num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {num_microbatches}"
            )
        self.num_microbatches = int(num_microbatches)

    def _role_spec(self, role: Optional[str], shape) -> PartitionSpec:
        if role == "pipe":  # shard the stacked stage dim (dim 0)
            return PartitionSpec(
                *([self.pipe_axis] + [None] * (len(shape) - 1))
            )
        return PartitionSpec()

    def put_params(self, params, hints=None):
        _check_pipe_divisible(
            params, hints, int(self.mesh.shape[self.pipe_axis]), self.pipe_axis
        )
        return super().put_params(params, hints)

    def comm_bytes_estimate(self, params, compute_dtype=None,
                            hints=None) -> dict:
        """Pipeline traffic (inheriting DataParallel's estimate would
        price the schedule's dominant cost — the per-tick activation
        ppermute — at literally zero). Two terms:

        - Gradient all-reduce over 'data' moves what each device HOLDS:
          full leaves for the replicated embeddings/head, a
          1/pipeline_parallel stage slice for 'pipe'-hinted stacks.
        - The schedule ppermutes one microbatch of activations per tick
          per stage boundary: M+n-2 sending ticks of
          ``mb_tokens x width x itemsize`` bytes each (GPipe; an
          interleaved schedule moves the same microbatches more laps over
          proportionally more ticks, so the per-step total is within the
          estimate's ignored constant factors, like the backward hops
          jax.grad's transposed schedule adds). Per TOKEN that is
          ``width x itemsize x (M+n-2) / M`` — the planner multiplies by
          the step's local token count. ``width`` (the activation's
          feature dim) is read off the pipe-hinted stacks: min shape[1]
          over their ndim>=3 leaves (a block's input-dim of its first
          matmul kernel — stacked (S, d_model, fan_out)); stacks with no
          such leaf price hops at zero rather than guess."""
        import jax.numpy as jnp

        n = int(self.mesh.shape[self.pipe_axis])
        data = int(self.mesh.shape[self.axis])
        m = max(int(self.num_microbatches), 1)
        grad = 0
        width = None

        def walk(p, h):
            nonlocal grad, width
            if isinstance(p, dict):
                for k, v in p.items():
                    walk(v, h.get(k, {}) if isinstance(h, dict) else h)
                return
            piped = h == "pipe" and n > 1
            nbytes = self._leaf_comm_bytes(p, compute_dtype)
            if data > 1:
                grad += nbytes // n if piped else nbytes
            if piped and len(getattr(p, "shape", ())) >= 3:
                w = int(p.shape[1])
                width = w if width is None else min(width, w)

        walk(params, hints or {})
        hop = 0
        if width is not None and n > 1:
            itemsize = jnp.dtype(
                compute_dtype if compute_dtype is not None else jnp.float32
            ).itemsize
            hop = width * itemsize * (m + n - 2) // m
        return self._comm_row(grad=grad, pipeline_hop_per_token=hop)


class DataSeqParallel(DataParallel):
    """Sequence (context) parallelism composed with data parallelism.

    Batches shard on 'data' AND their sequence (second) dimension on 'seq',
    so per-device activation memory is O(T / seq_parallel) — the long-
    context axis the reference never had (SURVEY.md §5: "the mesh design
    should merely not preclude adding a sequence axis" — here it is).
    MultiHeadAttention detects the seq axis at trace time and runs ring
    attention over it (ops.ring_attention): K/V blocks hop neighbor-to-
    neighbor over ICI instead of being all-gathered. Params replicated;
    gradient all-reduce spans both axes (every device holds a full replica).
    """

    def __init__(
        self,
        devices=None,
        *,
        mesh: Optional[Mesh] = None,
        seq_parallel: int = 2,
        axis: str = "data",
        seq_axis: str = "seq",
        attention: str = "ring",
    ):
        """``attention``: how MultiHeadAttention runs over the seq axis —
        "ring" (K/V blocks rotate neighbor-to-neighbor via ppermute; memory
        O(T/n) everywhere) or "ulysses" (two all-to-alls reshard tokens ->
        heads so each device computes full-T attention for H/n heads; one
        collective pair per layer instead of n-1 permutes, but needs
        num_heads divisible by seq_parallel)."""
        if attention not in ("ring", "ulysses"):
            raise ValueError(
                f"attention must be 'ring' or 'ulysses', got {attention!r}"
            )
        if mesh is None:
            ndev = len(devices or jax.devices())
            if ndev % seq_parallel:
                raise ValueError(
                    f"{ndev} devices not divisible by seq_parallel="
                    f"{seq_parallel}"
                )
            mesh = make_mesh(
                {axis: ndev // seq_parallel, seq_axis: seq_parallel},
                devices=devices,
            )
        super().__init__(mesh=mesh, axis=axis)
        if seq_axis not in mesh.axis_names:
            raise ValueError(f"Mesh {mesh.axis_names} has no axis {seq_axis!r}")
        self.seq_axis = seq_axis
        self.seq_attention = attention

    def batch_sharding(self):
        # Rank-dependent: applied per-leaf in put_batch.
        return NamedSharding(self.mesh, PartitionSpec(self.axis, self.seq_axis))

    def put_batch(self, batch, per_host: bool = False,
                  stacked: bool = False, async_: bool = False):
        return _put_batch_rows_seq(
            self.mesh, self.axis, self.seq_axis, batch, per_host, stacked
        )


class CompositeParallel(_HintedParallel):
    """General multi-axis parallelism: any subset of the mesh's canonical
    axes (data, fsdp, pipe, seq, expert, model) applied simultaneously.

    The pairwise strategies above each own 'data' plus one other axis; real
    large-model configs compose three or more (data x model x pipe,
    fsdp + model, ...). This strategy is the general form — SURVEY.md §2c's
    "a NamedSharding mesh makes DP one axis of a general design" carried to
    its conclusion. All hint roles resolve at once:

    - 'col'/'row'  -> Megatron TP over 'model' (last/first dim)
    - 'expert'     -> expert stack dim 0 over 'expert'
    - 'pipe'       -> stage stack dim 0 over 'pipe' (GPipe schedule in
                      nn.PipelinedBlocks; TP hints *inside* a pipelined
                      stack are subsumed by the stage sharding — put
                      TP-hinted layers outside the stack)
    - unhinted params additionally ZeRO-3-shard their largest divisible
      dim over 'fsdp' when that axis is present (role-assigned dims are
      never double-sharded).

    Batch rows shard over every batch-like axis present (('data','fsdp') —
    the standard hybrid recipe); the sequence dim shards over 'seq' with
    ring/Ulysses attention exactly as DataSeqParallel.
    """

    #: axes that shard batch rows (in canonical mesh order)
    BATCH_AXES = ("data", "fsdp")

    def __init__(
        self,
        axes: Optional[dict] = None,
        devices=None,
        *,
        mesh: Optional[Mesh] = None,
        num_microbatches: Optional[int] = None,
        seq_attention: str = "ring",
    ):
        from .mesh import AXES

        if mesh is None:
            if not axes:
                raise ValueError(
                    "CompositeParallel needs axis sizes, e.g. "
                    "CompositeParallel({'data': 2, 'model': 2, 'pipe': 2})"
                )
            mesh = make_mesh(dict(axes), devices=devices)
        unknown = set(mesh.axis_names) - set(AXES)
        if unknown:
            raise ValueError(
                f"Mesh axes {sorted(unknown)} are not canonical {AXES}"
            )
        row_axes = [a for a in self.BATCH_AXES if a in mesh.axis_names]
        if not row_axes:
            raise ValueError(
                "CompositeParallel needs at least one batch axis "
                f"({self.BATCH_AXES}) in the mesh; got {mesh.axis_names}"
            )
        # `axis` = the primary batch axis (what layers read for activation
        # sharding constraints); rows shard over ALL of row_axes.
        super().__init__(mesh=mesh, axis=row_axes[0])
        self._row_axes = tuple(row_axes)

        def present(name):
            return name if (
                name in mesh.axis_names and int(mesh.shape[name]) > 1
            ) else None

        self.model_axis = present("model")
        self.pipe_axis = present("pipe")
        self.seq_axis = present("seq")
        self.expert_axis = present("expert")
        self.fsdp_axis = present("fsdp")
        if seq_attention not in ("ring", "ulysses"):
            raise ValueError(
                f"attention must be 'ring' or 'ulysses', got {seq_attention!r}"
            )
        self.seq_attention = seq_attention
        if num_microbatches is None:
            num_microbatches = (
                int(mesh.shape[self.pipe_axis]) if self.pipe_axis else 1
            )
        if num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {num_microbatches}"
            )
        self.num_microbatches = int(num_microbatches)

    @property
    def num_replicas_in_sync(self) -> int:
        n = 1
        for a in self._row_axes:
            n *= int(self.mesh.shape[a])
        return n

    @property
    def row_axes(self) -> tuple:
        return self._row_axes

    # -- parameter placement -------------------------------------------------
    def _role_spec(self, role: Optional[str], shape) -> PartitionSpec:
        spec = [None] * len(shape)
        if role in ("col", "row") and self.model_axis:
            spec[-1 if role == "col" else 0] = self.model_axis
        elif role == "row1" and self.model_axis and len(shape) >= 2:
            # 'row' behind a stacked leading dim (ScannedBlocks).
            spec[1] = self.model_axis
        elif role == "expert" and self.expert_axis:
            spec[0] = self.expert_axis
        elif role == "pipe" and self.pipe_axis:
            spec[0] = self.pipe_axis
        if self.fsdp_axis and role != "pipe":
            # ZeRO-3 overlay on the largest free divisible dim. Pipelined
            # stacks are excluded: their shard_map in_specs mention only
            # 'pipe', so an fsdp overlay would just be re-gathered at the
            # shard_map boundary every step.
            n = int(self.mesh.shape[self.fsdp_axis])
            best, best_size = None, 0
            for d, size in enumerate(shape):
                if spec[d] is None and size % n == 0 and size > best_size:
                    best, best_size = d, size
            if best is not None:
                spec[best] = self.fsdp_axis
        return PartitionSpec(*spec)

    def put_params(self, params, hints=None):
        if self.pipe_axis:
            _check_pipe_divisible(
                params, hints, int(self.mesh.shape[self.pipe_axis]),
                self.pipe_axis,
            )
        # Unlike _HintedParallel, hints=None still shards (the fsdp
        # overlay applies to unhinted params too).
        return jax.device_put(params, self.params_sharding(params, hints))

    # -- batch placement -----------------------------------------------------
    def batch_sharding(self):
        return NamedSharding(self.mesh, PartitionSpec(self._row_axes))

    def put_batch(self, batch, per_host: bool = False,
                  stacked: bool = False, async_: bool = False):
        rows = self._row_axes if len(self._row_axes) > 1 else self._row_axes[0]
        return _put_batch_rows_seq(
            self.mesh, rows, self.seq_axis, batch, per_host, stacked
        )


# Alias keeping the reference's class name greppable for migrating users.
MultiWorkerMirroredStrategy = DataParallel
