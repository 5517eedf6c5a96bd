"""distributed_tpu — a TPU-native distributed training framework.

Capability parity with the reference system (Mrhs121/distributed: TF 2.0
MultiWorkerMirroredStrategy over TF_CONFIG/gRPC, driven from R, Python and
Spark — see SURVEY.md), re-designed for TPU: jit-compiled train steps,
device meshes + NamedSharding for parallelism, XLA collectives over ICI/DCN,
`jax.distributed` for multi-host bootstrap.

Quickstart (the reference's local->distributed 6-line-diff contract):

    import distributed_tpu as dtpu

    x, y = dtpu.data.load_mnist("train")
    model = dtpu.Model(dtpu.models.mnist_cnn())
    model.compile(optimizer=dtpu.optim.SGD(0.001),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=64, epochs=3)

    # distributed: wrap construction in a strategy scope
    strategy = dtpu.DataParallel()
    with strategy.scope():
        model = dtpu.Model(dtpu.models.mnist_cnn())
        model.compile(...)
    model.fit(x, y, batch_size=64 * strategy.num_replicas_in_sync, epochs=3)
"""

import time as _time

_import_t0 = _time.perf_counter()

from . import obs  # jax-free at import; spans resolve jax lazily

# The ``import`` phase of the span timeline: this file's first line to its
# last (docs/OBSERVABILITY.md "Span tracer").
_import_phase = obs.spans.begin("import", start=_import_t0)

from . import cluster, data, models, nn, ops, optim, parallel, precision, utils
from .precision import Policy
from .checkpoint import Checkpointer, ShardedCheckpointer, export_hdf5, import_hdf5
from .training import callbacks
from . import resilience  # after training/checkpoint: builds on both
from . import serving  # after training: Engine builds on Model
from .ops import losses, metrics
from .parallel.mesh import make_mesh
from .parallel.strategy import (
    CompositeParallel,
    DataParallel,
    DataPipelineParallel,
    DataSeqParallel,
    DataExpertParallel,
    DataTensorParallel,
    FSDP,
    FullyShardedDataParallel,
    MultiWorkerMirroredStrategy,
    SingleDevice,
    Strategy,
    ZeroDataParallel,
    current_strategy,
)
from .training.history import History
from .training.model import Model
from .version import __version__


def __getattr__(name):
    # `dtpu.quant` resolves lazily rather than via an eager top-level
    # import: the raw-speed tier (quant, and through optim.fused_adam /
    # ops.fused_update the Pallas optimizer kernel) must never add to the
    # base import cost on CPU boxes. quant itself is light (jnp only) and
    # usually already bound by nn's layer imports; the Pallas machinery
    # stays behind ops.__getattr__ until an API that needs it is called.
    if name in ("quant", "fleet", "rl"):
        # fleet (the multi-replica serving tier) and rl (online
        # post-training) are lazy for the same reason: processes that
        # only train or only serve never pay for them.
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Model",
    "History",
    "Strategy",
    "SingleDevice",
    "CompositeParallel",
    "DataParallel",
    "DataPipelineParallel",
    "DataSeqParallel",
    "DataExpertParallel",
    "DataTensorParallel",
    "FSDP",
    "FullyShardedDataParallel",
    "MultiWorkerMirroredStrategy",
    "ZeroDataParallel",
    "current_strategy",
    "make_mesh",
    "Checkpointer",
    "ShardedCheckpointer",
    "export_hdf5",
    "import_hdf5",
    "nn",
    "ops",
    "optim",
    "precision",
    "Policy",
    "losses",
    "metrics",
    "models",
    "data",
    "parallel",
    "cluster",
    "utils",
    "callbacks",
    "obs",
    "resilience",
    "serving",
    "fleet",  # lazy: see __getattr__
    "quant",  # lazy: see __getattr__
    "rl",  # lazy: see __getattr__
    "__version__",
]

# jax is imported by now: JAX's compile events feed the compile ledger, and
# the timeline and the ledger are written out at exit where a run has a
# dump location (obs.flight's rule; an unsupervised run has none).
obs.compile_ledger.install()
obs.flight.dump_timeline_at_exit()
_import_phase.end()
