"""paddle.distributed.spawn parity (python/paddle/distributed/spawn.py):
fork N local worker processes, set the PADDLE_* env contract, run `func`.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import traceback


def _worker(func, rank, nprocs, env, args, err_queue):
    for k, v in env.items():
        os.environ[k] = str(v)
    try:
        func(*args)
    except Exception:  # noqa: BLE001
        err_queue.put((rank, traceback.format_exc()))
        raise


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Launch `func(*args)` in `nprocs` processes with the same env contract
    the launch CLI exports (PADDLE_TRAINER_ID/.../PADDLE_TRAINER_ENDPOINTS).
    """
    if nprocs in (-1, 0, None):
        nprocs = int(os.environ.get("PADDLE_NPROC_PER_NODE", 1))
    from .launch.context import Node, check_one_process_per_tpu_host

    check_one_process_per_tpu_host(nprocs)
    ports = [Node.get_free_port() for _ in range(nprocs)]
    eps = [f"127.0.0.1:{p}" for p in ports]
    # reference default is 'spawn' (fresh interpreter — safe with the
    # multi-threaded XLA runtime in the parent); honor any explicit method
    ctx = mp.get_context(options.get("start_method", "spawn"))
    err_queue = ctx.Queue()
    procs = []
    for rank in range(nprocs):
        env = {
            "PADDLE_TRAINER_ID": rank,
            "PADDLE_TRAINERS_NUM": nprocs,
            "PADDLE_LOCAL_RANK": rank,
            "PADDLE_GLOBAL_RANK": rank,
            "PADDLE_GLOBAL_SIZE": nprocs,
            "PADDLE_LOCAL_SIZE": nprocs,
            "PADDLE_TRAINER_ENDPOINTS": ",".join(eps),
            "PADDLE_CURRENT_ENDPOINT": eps[rank],
        }
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, env, args, err_queue),
                        daemon=daemon)
        p.start()
        procs.append(p)

    class SpawnContext:
        def __init__(self, processes):
            self.processes = processes

        def join(self, timeout=None):
            import queue as _q

            for proc in self.processes:
                proc.join(timeout)
            failed = [i for i, proc in enumerate(self.processes)
                      if proc.exitcode not in (0, None)]
            if failed:
                # one traceback expected per failed rank; get() with a
                # timeout so in-flight feeder-thread data isn't dropped
                msgs = []
                for _ in failed:
                    try:
                        r, tb = err_queue.get(timeout=2)
                        msgs.append(f"--- rank {r} ---\n{tb}")
                    except _q.Empty:
                        break
                raise RuntimeError(
                    f"spawned ranks {failed} failed\n" + "\n".join(msgs))

    sc = SpawnContext(procs)
    if join:
        sc.join()
    return sc
