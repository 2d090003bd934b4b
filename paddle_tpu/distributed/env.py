"""Multi-host environment + rendezvous.

Parity: the reference's process-level bootstrap — launch.py env vars
(PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT,
/root/reference/python/paddle/distributed/launch.py:175) and ParallelEnv
(python/paddle/fluid/dygraph/parallel.py:54).  The nccl-id RPC rendezvous
(operators/collective/c_gen_nccl_id_op.cc:36) maps to
jax.distributed.initialize over DCN.
"""

import os

import jax

__all__ = ["ParallelEnv", "init_parallel_env", "get_rank", "get_world_size"]

_initialized = False


class ParallelEnv:
    """Parity: dygraph/parallel.py:54."""

    def __init__(self):
        self._nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        self._local_rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self._endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        self._current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def nranks(self):
        return self._nranks

    @property
    def local_rank(self):
        return self._local_rank

    @property
    def dev_id(self):
        return self._local_rank

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._endpoints.split(",") if self._endpoints else []


def init_parallel_env():
    """Multi-host init. On a single host this is a no-op (the mesh covers
    local devices); with PADDLE_TRAINER_ENDPOINTS set it performs the DCN
    rendezvous via jax.distributed.initialize (replacing gen_nccl_id's RPC
    broadcast)."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    env = ParallelEnv()
    if env.nranks > 1 and env.trainer_endpoints:
        coordinator = env.trainer_endpoints[0]
        kwargs = {}
        # bounded rendezvous (reference launch.py aborts the pack when a
        # worker dies; an unbounded initialize would hang instead)
        timeout = os.environ.get("PADDLE_RENDEZVOUS_TIMEOUT")
        if timeout:
            kwargs["initialization_timeout"] = int(timeout)
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=env.nranks,
            process_id=env.local_rank,
            **kwargs,
        )
    _initialized = True
    return env


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()
