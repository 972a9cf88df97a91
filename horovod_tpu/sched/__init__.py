"""Bucketed overlap scheduler: the gradient-exchange pipeline.

Horovod's headline capability is the *scheduling* around the allreduce
— tensor fusion, cycle-driven dispatch, compute/comms overlap (Sergeev
& Del Balso, arXiv:1802.05799 §4) — run by its background controller
loop.  Under XLA that loop has no process to live in; this package is
its trace-time replacement, three stages over one gradient pytree:

* ``plan``    — build a :class:`~horovod_tpu.sched.plan.BucketSchedule`:
                reverse-backward bucket order, dtype grouping via
                ``ops/fusion.bucket_plan``, per-bucket wire compression,
                ``allreduce`` vs ``reduce_scatter+all_gather`` exchange
                modes (the latter with ZeRO-1 shard updates, ``zero1``).
* ``execute`` — emit per-bucket collectives sequenced by
                ``lax.optimization_barrier`` and interleaved with the
                backward via ``jax.grad``-boundary taps (``hooks``), so
                XLA's latency-hiding scheduler overlaps wire time with
                the remaining compute.
* ``tune``    — wire ``utils/autotune.FusionAutotuner`` to the
                bucket-size knob, scoring windows from the metrics
                registry.
* ``store``   — persist converged (bucket_bytes, wire, lowering)
                winners to ``HVD_TPU_TUNE_DB``, keyed by (schedule
                signature, topology, jax version, knob fingerprint);
                the tuner warm-starts from a hit with zero exploration
                windows and the elastic driver serves entries
                fleet-wide (``/schedules``).  See docs/autotune.md.

``DistributedOptimizer`` and ``parallel.sync_gradients`` exchange
through this pipeline.  See docs/scheduler.md.
"""

from . import execute, hooks, plan, tune, zero1  # noqa: F401
from .execute import (  # noqa: F401
    exchange,
    hier_phase_factory,
    quantized_exchange_flat,
    sync_gradients_bucketed,
)
from .plan import (  # noqa: F401
    LOWER_CHOICES,
    WIRE_CHOICES,
    Bucket,
    BucketSchedule,
    SchedConfig,
    build_schedule,
    current_config,
    eligible_wire,
    resolve_lowering,
    set_config_override,
    wire_bytes,
)
from .store import ScheduleStore, knob_fingerprint, make_key  # noqa: F401
from .tune import ScheduleTuner  # noqa: F401
from .zero1 import bucketed_zero_step  # noqa: F401
