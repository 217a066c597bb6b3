"""In-process launchers: ``notebook_launcher`` and ``debug_launcher``
(reference ``launchers.py:38-296``).

The reference's notebook launcher must ``xmp.spawn`` 8 TPU processes or build a
torchelastic agent; in JAX one process drives every local chip, so on TPU the
"launch" is simply calling the function after (optionally) initializing
multi-host rendezvous.  Multi-process launching remains for the CPU debug rig:
``debug_launcher`` forks N processes that rendezvous over localhost with gloo
CPU collectives — the analog of the reference's ``start_processes`` + gloo
path used throughout its test suite (``launchers.py:263-296``).
"""

from __future__ import annotations

import multiprocessing
import os
import socket
import traceback
from typing import Any, Callable, Tuple


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker(fn: Callable, args: Tuple, rank: int, num_processes: int, port: int, error_queue) -> None:
    # Env must be set before any JAX backend initialization in this fresh
    # interpreter (spawn start method ⇒ jax is imported but uninitialized).
    os.environ["ACCELERATE_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    os.environ["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
    os.environ["ACCELERATE_PROCESS_ID"] = str(rank)
    os.environ["ACCELERATE_LOCAL_PROCESS_ID"] = str(rank)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("ACCELERATE_USE_CPU", "true")
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass  # older jax: collectives impl picked automatically
        # Rendezvous before user code, like the reference's PrepareForLaunch
        # bootstrap (utils/launch.py:585-627) — fn() then sees the full world
        # whether or not it constructs a PartialState.
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=num_processes,
            process_id=rank,
        )
        fn(*args)
    except Exception:
        error_queue.put(f"rank {rank}:\n{traceback.format_exc()}")
        raise


def debug_launcher(function: Callable, args: Tuple = (), num_processes: int = 2) -> None:
    """Launch ``function`` in ``num_processes`` CPU processes with a real
    cross-process JAX runtime (reference ``debug_launcher``, ``launchers.py:263-296``).

    Each worker gets ``ACCELERATE_COORDINATOR_ADDRESS``/``_PROCESS_ID`` env so a
    plain ``Accelerator()``/``PartialState()`` inside ``function`` performs the
    multi-host rendezvous exactly as it would on a pod.
    """
    port = _free_port()
    ctx = multiprocessing.get_context("spawn")
    error_queue = ctx.SimpleQueue()
    procs = []
    for rank in range(num_processes):
        p = ctx.Process(target=_worker, args=(function, args, rank, num_processes, port, error_queue))
        p.start()
        procs.append(p)
    failed = []
    for rank, p in enumerate(procs):
        p.join()
        if p.exitcode != 0:
            failed.append(rank)
    if failed:
        errors = []
        while not error_queue.empty():
            errors.append(error_queue.get())
        raise RuntimeError(
            f"debug_launcher workers {failed} failed:\n" + "\n".join(errors)
        )


def notebook_launcher(
    function: Callable,
    args: Tuple = (),
    num_processes: int = None,
    mixed_precision: str = "no",
    use_port: str = "29500",
    master_addr: str = "127.0.0.1",
    node_rank: int = 0,
    num_nodes: int = 1,
) -> Any:
    """Launch training from a notebook (reference ``launchers.py:38-260``).

    On TPU the JAX runtime is single-process-per-host and already owns every
    local chip, so unlike torch_xla there is nothing to spawn: the function is
    invoked directly after setting the requested precision/topology env.  With
    ``num_processes > 1`` on CPU this degrades to :func:`debug_launcher` (the
    reference's CPU fork path).
    """
    import jax

    if mixed_precision not in ("no", "bf16", "fp16"):
        raise ValueError(f"Unknown mixed_precision mode: {mixed_precision}")
    os.environ["ACCELERATE_MIXED_PRECISION"] = mixed_precision
    if num_nodes > 1:
        # Multi-host notebook: rendezvous with the pod's coordinator.
        os.environ["ACCELERATE_COORDINATOR_ADDRESS"] = f"{master_addr}:{use_port}"
        os.environ["ACCELERATE_NUM_PROCESSES"] = str(num_nodes)
        os.environ["ACCELERATE_PROCESS_ID"] = str(node_rank)
        return function(*args)
    # a backend that fails to start raises here — it is not reported as "cpu"
    platform = jax.devices()[0].platform
    if platform == "cpu" and num_processes and num_processes > 1:
        return debug_launcher(function, args, num_processes)
    if num_processes and num_processes > 1:
        raise ValueError(
            "On TPU one JAX process drives all local chips — num_processes > 1 is only "
            "meaningful on CPU (debug) or across hosts (num_nodes)."
        )
    return function(*args)
