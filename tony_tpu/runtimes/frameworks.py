"""Concrete framework runtimes (see base.py module docstring for the map to
``TaskExecutor.java:161-207``)."""

from __future__ import annotations

import json
import os
from typing import Dict, List

from tony_tpu import constants
from tony_tpu.conf.config import TonyTpuConfig
from tony_tpu.runtimes.base import (Runtime, TaskIdentity, flatten_spec,
                                    register)


@register
class JaxRuntime(Runtime):
    """TPU-native runtime: bootstrap for ``jax.distributed.initialize``.

    The cluster-spec barrier already guarantees every process knows every
    host:port, so the coordination service address is simply the
    globally-first task's advertised endpoint; process ids follow the
    global-rank contract. This single mechanism replaces TF_CONFIG /
    MASTER_ADDR / DMLC_* for JAX jobs (SURVEY.md §2.4), and XLA collectives
    over ICI/DCN become the data plane.
    """

    name = "jax"

    def framework_env(self, cluster_spec: Dict[str, List[str]],
                      me: TaskIdentity, conf: TonyTpuConfig) -> Dict[str, str]:
        flat = flatten_spec(cluster_spec)
        my_id = f"{me.job_name}:{me.index}"
        rank = flat.index(my_id)
        job0, _, idx0 = flat[0].partition(":")
        coordinator = cluster_spec[job0][int(idx0)]
        env = {
            constants.JAX_COORDINATOR_ADDRESS: coordinator,
            constants.JAX_NUM_PROCESSES: str(len(flat)),
            constants.JAX_PROCESS_ID: str(rank),
        }
        from tony_tpu.conf import keys as K

        # Persistent XLA compile cache: a HOST-stable path, so the second
        # job on a TPU VM skips the first's compiles. The user's own env
        # wins (task env inherits the executor's os.environ, which carries
        # EXECUTION_ENV); empty key disables.
        cache_dir = str(conf.get(K.JAX_COMPILE_CACHE_DIR, "") or "").strip()
        if cache_dir and constants.JAX_COMPILATION_CACHE_DIR \
                not in os.environ:
            env[constants.JAX_COMPILATION_CACHE_DIR] = \
                os.path.expanduser(cache_dir)
        return env


@register
class TensorFlowRuntime(Runtime):
    """TF_CONFIG + legacy CLUSTER_SPEC (reference ``Utils.constructTFConfig``
    :491-501 and ``TaskExecutor.java:161-168``)."""

    name = "tensorflow"

    def framework_env(self, cluster_spec: Dict[str, List[str]],
                      me: TaskIdentity, conf: TonyTpuConfig) -> Dict[str, str]:
        tf_config = {
            "cluster": cluster_spec,
            "task": {"type": me.job_name, "index": me.index},
            "environment": "cloud",
        }
        return {constants.TF_CONFIG: json.dumps(tf_config, sort_keys=True)}


@register
class PyTorchRuntime(Runtime):
    """torch.distributed TCP rendezvous (reference ``TaskExecutor.java:169-179``
    + ``Utils.parseClusterSpecForPytorch`` :575-585): INIT_METHOD points at the
    globally-first task; RANK/WORLD follow the global ordering. Also exports
    MASTER_ADDR/MASTER_PORT/WORLD_SIZE for modern torchrun-style scripts and
    torch_xla's xla:// rendezvous."""

    name = "pytorch"

    def framework_env(self, cluster_spec: Dict[str, List[str]],
                      me: TaskIdentity, conf: TonyTpuConfig) -> Dict[str, str]:
        flat = flatten_spec(cluster_spec)
        rank = flat.index(f"{me.job_name}:{me.index}")
        job0, _, idx0 = flat[0].partition(":")
        master = cluster_spec[job0][int(idx0)]
        host, _, port = master.rpartition(":")
        return {
            constants.INIT_METHOD: f"tcp://{master}",
            constants.RANK: str(rank),
            constants.WORLD: str(len(flat)),
            constants.MASTER_ADDR: host,
            constants.MASTER_PORT: port,
            constants.WORLD_SIZE: str(len(flat)),
        }


@register
class MXNetRuntime(Runtime):
    """DMLC_* parameter-server env (reference ``TaskExecutor.java:180-200`` +
    ``Utils`` :587-609): the ``scheduler`` task's address is the PS root; roles
    come from jobtype names scheduler/server/worker."""

    name = "mxnet"

    def framework_env(self, cluster_spec: Dict[str, List[str]],
                      me: TaskIdentity, conf: TonyTpuConfig) -> Dict[str, str]:
        sched = cluster_spec.get(constants.SCHEDULER_JOB_NAME, [])
        if not sched:
            raise ValueError("mxnet runtime requires a 'scheduler' jobtype")
        host, _, port = sched[0].rpartition(":")
        return {
            constants.DMLC_PS_ROOT_URI: host,
            constants.DMLC_PS_ROOT_PORT: port,
            constants.DMLC_ROLE: me.job_name,
            constants.DMLC_NUM_SERVER: str(
                len(cluster_spec.get(constants.SERVER_JOB_NAME, []))),
            constants.DMLC_NUM_WORKER: str(
                len(cluster_spec.get(constants.WORKER_JOB_NAME, []))),
            constants.DMLC_USE_KUBERNETES: "0",
        }


@register
class HorovodRuntime(Runtime):
    """Horovod does its own MPI/gloo rendezvous inside the user command —
    nothing to export (reference ``TaskExecutor.java:201-204``)."""

    name = "horovod"
