"""Process environment (reference env contract: PADDLE_TRAINER_ID,
PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ENDPOINTS, PADDLE_MASTER — see
python/paddle/distributed/parallel.py init_parallel_env and
launch/context/__init__.py).

On TPU, one process per HOST drives all local chips (single-controller JAX);
the launcher keeps the same env names so reference-shaped scripts run.
"""
import os

import jax

from ..utils.envs import env_bool, env_str

_initialized = False


def get_rank():
    return int(env_str("PADDLE_TRAINER_ID", os.environ.get("RANK", "0")) or 0)


def get_world_size():
    ws = env_str("PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE"))
    if ws is not None:
        return int(ws)
    return 1


def get_local_rank():
    return int(env_str("PADDLE_LOCAL_RANK", os.environ.get("LOCAL_RANK", "0")) or 0)


def get_master_endpoint():
    ep = env_str("PADDLE_MASTER") or os.environ.get("MASTER_ENDPOINT")
    if ep:
        return ep
    eps = env_str("PADDLE_TRAINER_ENDPOINTS", "")
    if eps:
        return eps.split(",")[0]
    addr = os.environ.get("MASTER_ADDR")
    port = os.environ.get("MASTER_PORT")
    if addr and port:
        return f"{addr}:{port}"
    return None


def is_initialized():
    return _initialized


def init_distributed(timeout_s=900):
    """jax.distributed.initialize over the Paddle env contract (reference
    analogue: TCPStore rendezvous + ncclCommInitRank, SURVEY.md §3.2)."""
    global _initialized
    if _initialized:
        return
    # the launched worker's bootstrap is an entry point: place the
    # persistent compile cache (every restart/regrow recompiles otherwise)
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    world = get_world_size()
    if world > 1 and not env_bool("PADDLE_TPU_SKIP_JAX_DIST"):
        coordinator = get_master_endpoint()
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world,
            process_id=get_rank(),
        )
    _initialized = True
