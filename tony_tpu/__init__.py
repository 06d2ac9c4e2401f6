"""tony-tpu: a TPU-native framework for orchestrating distributed deep-learning jobs.

tony-tpu fills the role the reference framework (TonY — see /root/reference,
``README.md``) fills for Hadoop/YARN clusters, re-designed from scratch for TPU
hardware and the JAX/XLA execution model:

- A **job coordinator** (the ApplicationMaster analogue,
  reference ``tony-core/src/main/java/com/linkedin/tony/ApplicationMaster.java``)
  gang-schedules jobtypes over a slice inventory, runs the cluster-spec
  rendezvous barrier, monitors heartbeats and applies failure policy.
- A **task executor** (reference ``TaskExecutor.java``) supervises one user
  process per task, wiring the framework-specific environment contract
  (JAX coordination service, TF_CONFIG, torch rendezvous, DMLC_*).
- A **client library + CLI** (reference ``TonyClient.java``,
  ``tony-cli/``) merges layered configs into a frozen artifact, validates
  resource quotas, submits, and mirrors task state to listeners.
- A **parallelism library** (new work — absent from the reference, see
  SURVEY.md §2.3) owns what TonY delegated to user frameworks: device meshes,
  DP/FSDP/TP/PP/EP and sequence/context parallelism with ring attention,
  implemented with jax.sharding / shard_map / pallas.

Unlike the reference, the data plane and the orchestration plane meet here:
XLA collectives over ICI/DCN are the communication backend, bootstrapped by
the coordinator's rendezvous (replacing four env-var dialects with one).
"""

__version__ = "0.1.0"

# With TONY_LOCK_SANITIZER=1 in the environment, arm the lock sanitizer
# BEFORE any tony_tpu module allocates a lock (telemetry below has
# module-level locks), so executor/coordinator/pool subprocesses of a
# sanitized run join the lock-order/hazard verdict; no-op — one env read
# — everywhere else.
from tony_tpu.devtools import sanitizer as _sanitizer  # noqa: E402

_sanitizer.maybe_enable_from_env()

# Same contract for the data-race detector (TONY_RACE_DETECTOR=1,
# devtools/race.py): it must arm BEFORE the @guarded control-plane
# classes are defined (decoration is the instrumentation point) and
# before any thread starts, so subprocesses of an armed run join the
# suite-wide race verdict; no-op — one env read — everywhere else.
from tony_tpu.devtools import race as _race  # noqa: E402

_race.maybe_enable_from_env()

from tony_tpu import constants  # noqa: F401
from tony_tpu.conf.config import TonyTpuConfig  # noqa: F401

# Inside a task (TONY_METRICS_FILE set by the executor) a bare import is
# enough to start the HBM telemetry reporter; no-op everywhere else.
from tony_tpu import telemetry as _telemetry  # noqa: E402

_telemetry.maybe_start()

# Inside a task (TONY_STACKDUMP_SIGNAL set by the executor) the same bare
# import pre-registers the hung-task diagnostics handler: the coordinator's
# progress liveness can then get an all-thread stack dump out of a wedged
# user process before killing it; no-op everywhere else.
_telemetry.install_stack_dump_handler()

# Inside a task whose supervisor exported TONY_FAULTS, arm the fault
# harness for this process too (user scripts' checkpoint/storage calls are
# injection sites); no-op — one env read — everywhere else.
from tony_tpu import faults as _faults  # noqa: E402

_faults.install_from_env()

# Inside a task, process start → here is the first span of the user
# process's boot (``user.pre_import``, telemetry.py); no-op — one env read
# — everywhere else.
_telemetry.mark_import_done()
