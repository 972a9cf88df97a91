"""horovod_tpu: a TPU-native distributed training framework.

Brand-new JAX/XLA re-design with the capabilities of Horovod
(reference: maxhgerlach/horovod v0.22.1, the process-sets fork): the
``hvd.*`` API surface (init/rank/size/process sets, allreduce /
allgather / broadcast / alltoall / reducescatter, DistributedOptimizer,
Adasum, compression, elastic training, timeline, autotune, launcher) —
built on ``jax.sharding.Mesh`` + ``shard_map`` + XLA collectives over
ICI/DCN instead of a background MPI/NCCL negotiation service.

Typical use (the reference MNIST pattern, ``examples/pytorch/pytorch_mnist.py``)::

    import horovod_tpu as hvd
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    step = hvd.distributed_train_step(loss_fn, tx)
"""

from .version import __version__  # noqa: F401

from . import runtime as _runtime
from .exceptions import (  # noqa: F401
    CheckpointCorruptionError,
    CheckpointMissingKeysError,
    FaultInjected,
    HorovodInternalError,
    HorovodTpuError,
    HostsUpdatedInterrupt,
    NotInitializedError,
    QuantizedWireError,
    RetryTimeoutError,
)
from .process_sets import ProcessSet  # noqa: F401
from .runtime import WORLD_AXIS  # noqa: F401
from . import ops  # noqa: F401
from .ops import traced  # noqa: F401
from .ops.eager import (  # noqa: F401
    Adasum,
    Average,
    Handle,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
    allgather,
    allgather_async,
    allgather_v,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    grouped_allreduce,
    grouped_allreduce_async,
    join,
    poll,
    reducescatter,
    synchronize,
)
from .ops.sparse import (  # noqa: F401
    IndexedSlices,
    dense_grad_to_indexed_slices,
    sparse_allreduce,
    sparse_allreduce_eager,
)
from .ops.quantized import (  # noqa: F401
    quantized_all_gather,
    quantized_allreduce,
    quantized_allreduce_ef,
    quantized_reduce_scatter,
)

init = _runtime.init
shutdown = _runtime.shutdown
is_initialized = _runtime.is_initialized


# ---- Topology queries (reference HorovodBasics, common/basics.py:29) ----

def size() -> int:
    """Total number of ranks (TPU chips) in the world."""
    return _runtime.get_runtime().size


def rank() -> int:
    """Global rank of this process's first chip (== reference process rank
    when running one chip per process)."""
    return _runtime.get_runtime().rank


def local_rank() -> int:
    return _runtime.get_runtime().local_rank


def local_size() -> int:
    """Chips attached to this host."""
    return _runtime.get_runtime().local_size


def cross_rank() -> int:
    """Host index (reference cross communicator rank)."""
    return _runtime.get_runtime().cross_rank


def cross_size() -> int:
    return _runtime.get_runtime().cross_size


def process_rank() -> int:
    """This controller process's index (jax.process_index)."""
    return _runtime.get_runtime().process_rank


def process_count() -> int:
    return _runtime.get_runtime().process_count


def mesh():
    """The global 1-D ``jax.sharding.Mesh`` (the world communicator)."""
    return _runtime.get_runtime().mesh


def is_homogeneous() -> bool:
    """True when every host has the same number of chips (reference
    ``horovod_is_homogeneous``)."""
    rt = _runtime.get_runtime()
    return rt.size == rt.local_size * rt.cross_size


# ---- Capability flags (reference horovod_*_built / *_enabled,
# common/basics.py) — the non-TPU backends report absent ----

def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def tpu_enabled() -> bool:
    import jax

    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


# ---- Process sets (reference common/process_sets.py) ----

def add_process_set(ranks_or_set) -> ProcessSet:
    """Register a new process set after init (requires
    HVD_TPU_DYNAMIC_PROCESS_SETS=1, mirroring the reference gate)."""
    ps = ranks_or_set if isinstance(ranks_or_set, ProcessSet) else ProcessSet(ranks_or_set)
    return _runtime.get_runtime().process_set_table.add(ps)


def remove_process_set(ps: ProcessSet) -> None:
    _runtime.get_runtime().process_set_table.remove(ps)


def get_process_set_ids():
    return _runtime.get_runtime().process_set_table.ids()


def global_process_set() -> ProcessSet:
    return _runtime.get_runtime().process_set_table.global_set


# ---- Optimizer / functions (populated by submodules) ----
from .optim import (  # noqa: F401,E402
    DistributedAdasumOptimizer,
    DistributedOptimizer,
    distributed_train_step,
    fsdp_train_step,
    zero_train_step,
)
from .functions import (  # noqa: F401,E402
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    broadcast_variables,
)
from . import compression  # noqa: F401,E402
from .compression import Compression  # noqa: F401,E402
from . import sched  # noqa: F401,E402
from .sched import SchedConfig  # noqa: F401,E402
from . import topo  # noqa: F401,E402
from . import xir  # noqa: F401,E402
from . import svc  # noqa: F401,E402
from . import trace  # noqa: F401,E402
from . import elastic  # noqa: F401,E402
from .sync_batch_norm import SyncBatchNorm  # noqa: F401,E402
from . import metrics  # noqa: F401,E402
from .metrics import (  # noqa: F401,E402
    get_counter,
    get_counters,
    get_gauge,
    get_histogram,
    inc_counter,
    metric_average,
    observe,
    render_prometheus,
    reset_counters,
    set_gauge,
)
from . import events  # noqa: F401,E402
from . import faults  # noqa: F401,E402
from .utils.retry import RetryPolicy  # noqa: F401,E402
from .utils.timeline import (  # noqa: F401,E402
    merge_timeline_files,
    profile_bucket_step,
    start_timeline,
    stop_timeline,
)
from . import callbacks  # noqa: F401,E402
from . import data  # noqa: F401,E402
from . import checkpoint  # noqa: F401,E402
from .checkpoint import (  # noqa: F401,E402
    latest_good_step,
    load_checkpoint,
    load_params,
    restore_or_init,
    save_checkpoint,
    verify_checkpoint,
)
from . import serve  # noqa: F401,E402
